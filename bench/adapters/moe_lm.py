"""``moe_lm`` as the program runs it: every layer attention + MoE, so the
program stacks one period of one layer kind."""
from __future__ import annotations

from typing import Dict

import jax

from bench.reference import moe_lm as ref


def check(cfg, m: Dict) -> Dict:
    a, mo = cfg.attn, cfg.moe
    got = {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
           "vocab_size": cfg.vocab_size, "norm_eps": cfg.norm_eps,
           "tie_embeddings": cfg.tie_embeddings,
           "param_dtype": cfg.param_dtype,
           "n_heads": a.n_heads, "n_kv_heads": a.n_kv_heads,
           "head_dim": a.head_dim, "rope_theta": a.rope_theta,
           "num_experts": mo.num_experts, "top_k": mo.top_k,
           "d_expert": mo.d_expert, "aux_loss_coef": mo.aux_loss_coef}
    bad = {k: (got[k], m[k]) for k in got if got[k] != m[k]}
    # what the architecture has none of
    for k, v, want in (("activation", cfg.activation, "swiglu"),
                       ("d_ff", cfg.d_ff, 0),
                       ("layer_pattern", cfg.layer_pattern, ""),
                       ("num_shared_experts", mo.num_shared_experts, 0),
                       ("every_k_layers", mo.every_k_layers, 1),
                       ("router_norm_topk", mo.router_norm_topk, True),
                       ("qkv_bias", a.qkv_bias, False),
                       ("window", a.window, 0)):
        if v != want:
            bad[k] = (v, want)
    return bad


def _to_program(layers: Dict, top: Dict, W: int):
    """Canonical stacked weights -> the program's parameter tree (one
    period: every layer is attention + MoE)."""
    L, E = layers["w_gate"].shape[:2]

    def shard(w):                      # (L, E, a, b) -> (L, W, E/W, a, b)
        return w.reshape((L, W, E // W) + w.shape[2:])

    layer = {"ln1": {"scale": layers["ln1"]},
             "attn": {n: layers[n] for n in ("wq", "wk", "wv", "wo")},
             "ln2": {"scale": layers["ln2"]},
             "moe": {"router": layers["router"],
                     "experts": {n: shard(layers[n])
                                 for n in ("w_gate", "w_up", "w_down")}}}
    out = {"embed": top["embed"], "ln_f": {"scale": top["ln_f"]},
           "layers": [layer]}
    if "lm_head" in top:
        out["lm_head"] = top["lm_head"]
    return out


def build(m: Dict, init: Dict, layer_keys, top_key, W: int):
    layers = jax.vmap(lambda k: ref.make_layer(m, init, k))(layer_keys)
    return _to_program(layers, ref.make_top(m, init, top_key), W)
