"""The seam between the harness and the system under test: the program's
model configuration built from a configuration file, and the program's
parameter tree filled with the weights the reference makes from the seed
(one jitted call, on the device, in the dtype they are served in)."""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict

import jax
import jax.numpy as jnp

from bench.reference import moe_lm

CONFIG_DIR = Path(__file__).resolve().parent / "configs"


def load_config(name: str) -> Dict:
    path = CONFIG_DIR / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"configuration {name!r}: no file {path}")
    return json.loads(path.read_text())


def model_spec(conf: Dict) -> Dict:
    """The reference's view of the model (its own key names), from the
    configuration file's published keys and what it states as run."""
    hf = conf
    return moe_lm.frozen({
        "n_layers": hf["num_hidden_layers"], "d_model": hf["hidden_size"],
        "n_heads": hf["num_attention_heads"],
        "n_kv_heads": hf["num_key_value_heads"],
        "head_dim": hf["hidden_size"] // hf["num_attention_heads"],
        "vocab_size": hf["vocab_size"],
        "num_experts": hf["num_local_experts"],
        "top_k": hf["num_experts_per_tok"],
        "d_expert": hf["intermediate_size"],
        "tie_embeddings": hf["tie_word_embeddings"],
        "rope_theta": float(hf["rope_theta"]),
        "norm_eps": float(hf["rms_norm_eps"]),
        "aux_loss_coef": float(hf["router_aux_loss_coef"]),
        "param_dtype": conf["program"]["param_dtype"],
    })


def program_config(conf: Dict):
    """The program's ``ModelConfig``: the registered arch with the file's
    changes, and the MoE schedule pinned (no plan file, no cost model)."""
    from repro.configs.base import get_config

    p = conf["program"]
    cfg = get_config(p["arch"])
    changes = dict(p.get("changes", {}))
    if "attn" in changes:
        changes["attn"] = dataclasses.replace(cfg.attn, **changes["attn"])
    cfg = dataclasses.replace(cfg, **changes)
    moe = dataclasses.replace(cfg.moe, plan_override=True, plan_cache="",
                              **p.get("moe_knobs", {}))
    cfg = dataclasses.replace(cfg, moe=moe)
    m = model_spec(conf)
    want = {"n_layers": m["n_layers"], "d_model": m["d_model"],
            "vocab_size": m["vocab_size"], "norm_eps": m["norm_eps"],
            "tie_embeddings": m["tie_embeddings"],
            "param_dtype": m["param_dtype"]}
    got = {k: getattr(cfg, k) for k in want}
    a, mo = cfg.attn, cfg.moe
    want.update(n_heads=m["n_heads"], n_kv_heads=m["n_kv_heads"],
                head_dim=m["head_dim"], rope_theta=m["rope_theta"],
                num_experts=m["num_experts"], top_k=m["top_k"],
                d_expert=m["d_expert"], aux_loss_coef=m["aux_loss_coef"])
    got.update(n_heads=a.n_heads, n_kv_heads=a.n_kv_heads,
               head_dim=a.head_dim, rope_theta=a.rope_theta,
               num_experts=mo.num_experts, top_k=mo.top_k,
               d_expert=mo.d_expert, aux_loss_coef=mo.aux_loss_coef)
    bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
    if bad or cfg.activation != "swiglu" or cfg.d_ff or cfg.layer_pattern \
            or mo.num_shared_experts or mo.every_k_layers != 1 \
            or not mo.router_norm_topk or a.qkv_bias or a.window:
        raise ValueError(f"{conf['name']}: the program's config departs from"
                         f" the file (program, file): {bad}")
    return cfg


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


def make_program_params(conf: Dict, seed: int, ctx):
    """The program's parameters, made on the device in one jitted call
    from the seed; checked against the program's own abstract tree."""
    from repro.models import lm

    m = model_spec(conf)
    init = moe_lm.frozen(conf["init"])
    W = ctx.model_size if ctx.active else 1
    keys = jnp.stack([moe_lm.layer_key(seed, l)
                      for l in range(m["n_layers"])])
    tkey = moe_lm.top_key(seed)

    def build(keys, tkey):
        layers = jax.vmap(lambda k: moe_lm.make_layer(m, init, k))(keys)
        return _to_program(layers, moe_lm.make_top(m, init, tkey), W)

    cfg = program_config(conf)
    want = lm.abstract_params(cfg, ctx)
    got = jax.eval_shape(build, keys, tkey)
    sig = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda x: (x.shape, jnp.dtype(x.dtype).name), t)
    if jax.tree_util.tree_structure(want) != \
            jax.tree_util.tree_structure(got) or sig(want) != sig(got):
        raise ValueError("the program's parameter tree changed layout; "
                         "bench/program.py must follow it")
    return jax.jit(build)(keys, tkey)
