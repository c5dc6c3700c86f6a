"""``lead_dense_moe`` (``ref_lead_dense_moe.py``) as the program runs it:
a dense FFN (``d_ff``) on the layers that are not MoE layers, the MoE
every ``every_k_layers`` from ``layer_offset``, and the shared experts
as the MoE's ``shared`` FFN. The program stacks layers by period, so this
adapter takes the layouts where one period holds every layer (a leading
dense layer is ``every_k_layers=2, layer_offset=1`` at 2 layers)."""
from __future__ import annotations

from typing import Dict

import jax

import ref_lead_dense_moe as ref


def check(cfg, m: Dict) -> Dict:
    from repro.models.lm import period_of

    a, mo = cfg.attn, cfg.moe
    L = cfg.n_layers
    got = {"n_layers": L, "d_model": cfg.d_model,
           "vocab_size": cfg.vocab_size, "norm_eps": cfg.norm_eps,
           "tie_embeddings": cfg.tie_embeddings,
           "param_dtype": cfg.param_dtype, "d_ff": cfg.d_ff,
           "n_heads": a.n_heads, "n_kv_heads": a.n_kv_heads,
           "head_dim": a.head_dim, "rope_theta": a.rope_theta,
           "num_experts": mo.num_experts, "top_k": mo.top_k,
           "d_expert": mo.d_expert, "n_shared": mo.num_shared_experts}
    bad = {k: (got[k], m[k]) for k in got if got[k] != m[k]}
    moe = [cfg.is_moe_layer(i) for i in range(L)]
    want = [not ref.is_dense(m, i) for i in range(m["n_layers"])]
    if moe != want:
        bad["moe_layers"] = (moe, want)
    for k, v, w in (("activation", cfg.activation, "swiglu"),
                    ("layer_pattern", cfg.layer_pattern, ""),
                    ("period", period_of(cfg), L),
                    ("router_norm_topk", mo.router_norm_topk, True),
                    ("qkv_bias", a.qkv_bias, False),
                    ("window", a.window, 0)):
        if v != w:
            bad[k] = (v, w)
    return bad


def _program_layer(w: Dict, W: int) -> Dict:
    p = {"ln1": {"scale": w["ln1"]},
         "attn": {n: w[n] for n in ("wq", "wk", "wv", "wo")},
         "ln2": {"scale": w["ln2"]}}
    if "router" not in w:
        p["ffn"] = {n: w[n] for n in ("w_gate", "w_up", "w_down")}
        return p
    E = w["w_gate"].shape[0]
    p["moe"] = {"router": w["router"],
                "experts": {n: w[n].reshape((W, E // W) + w[n].shape[1:])
                            for n in ("w_gate", "w_up", "w_down")},
                "shared": {f"w_{n}": w[f"s_{n}"]
                           for n in ("gate", "up", "down")}}
    return p


def build(m: Dict, init: Dict, layer_keys, top_key, W: int):
    layers = [jax.tree_util.tree_map(
        lambda x: x[None],
        _program_layer(ref.make_layer(m, init, layer_keys[l], l), W))
        for l in range(m["n_layers"])]
    top = ref.make_top(m, init, top_key)
    out = {"embed": top["embed"], "ln_f": {"scale": top["ln_f"]},
           "layers": layers}
    if "lm_head" in top:
        out["lm_head"] = top["lm_head"]
    return out
