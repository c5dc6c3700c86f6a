"""Plain reference of a test-only architecture, ``lead_dense_moe``: the
first ``first_k_dense_replace`` layers have a dense SwiGLU MLP, every
later layer a top-k MoE (``moe_lm``'s) plus ``n_shared_experts`` ungated
shared experts, one SwiGLU of their summed width beside the routed ones.
The configuration keys are DeepSeek-V2's names for these. Built on
``moe_lm``'s reference pieces; it imports nothing of the program."""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from bench.reference import moe_lm as base
from bench.reference.moe_lm import (embed, layer_key, logits,  # noqa: F401
                                    top_key, top_weights)

F32 = jnp.float32


def spec(conf: Dict):
    hf = conf
    return base.frozen({
        "n_layers": hf["num_hidden_layers"], "d_model": hf["hidden_size"],
        "n_heads": hf["num_attention_heads"],
        "n_kv_heads": hf["num_key_value_heads"],
        "head_dim": hf["hidden_size"] // hf["num_attention_heads"],
        "vocab_size": hf["vocab_size"],
        "num_experts": hf["n_routed_experts"],
        "top_k": hf["num_experts_per_tok"],
        "d_expert": hf["moe_intermediate_size"],
        "n_shared": hf["n_shared_experts"],
        "d_ff": hf["intermediate_size"],
        "first_dense": hf["first_k_dense_replace"],
        "tie_embeddings": hf["tie_word_embeddings"],
        "rope_theta": float(hf["rope_theta"]),
        "norm_eps": float(hf["rms_norm_eps"]),
        "param_dtype": conf["program"]["param_dtype"],
    })


def is_dense(m: Dict, l: int) -> bool:
    return l < m["first_dense"]


def _swiglu_shapes(d: int, f: int, prefix: str) -> Dict[str, tuple]:
    return {f"{prefix}gate": (d, f), f"{prefix}up": (d, f),
            f"{prefix}down": (f, d)}


def _normal(key, shape, pdt):
    z = jax.random.normal(key, shape, F32)
    return (z / math.sqrt(shape[-2])).astype(pdt)


def make_dense_layer(m: Dict, init: Dict, key) -> Dict[str, jnp.ndarray]:
    """Attention and a dense SwiGLU of width ``d_ff``."""
    d = m["d_model"]
    qd, kvd = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    shapes = {"wq": (d, qd), "wk": (d, kvd), "wv": (d, kvd), "wo": (qd, d),
              **_swiglu_shapes(d, m["d_ff"], "w_")}
    keys = jax.random.split(key, len(shapes) + 2)
    pdt = jnp.dtype(m["param_dtype"])
    out = {name: _normal(k, shape, pdt)
           for k, (name, shape) in zip(keys[2:], shapes.items())}
    for k, name in zip(keys[:2], ("ln1", "ln2")):
        out[name] = 1.0 + init["norm_jitter"] * jax.random.normal(k, (d,), F32)
    return out


def make_moe_layer(m: Dict, init: Dict, key) -> Dict[str, jnp.ndarray]:
    """``moe_lm``'s layer and the shared experts' SwiGLU (``s_*``)."""
    out = base.make_layer(m, init, key)
    shapes = _swiglu_shapes(m["d_model"], m["n_shared"] * m["d_expert"],
                            "s_")
    keys = jax.random.split(jax.random.fold_in(key, 1), len(shapes))
    pdt = jnp.dtype(m["param_dtype"])
    out.update({name: _normal(k, shape, pdt)
                for k, (name, shape) in zip(keys, shapes.items())})
    return out


def make_layer(m: Dict, init: Dict, key, l: int):
    return (make_dense_layer if is_dense(m, l) else make_moe_layer)(
        m, init, key)


def layer_weights(m: Dict, init: Dict, seed: int, l: int):
    return jax.jit(make_layer, static_argnums=(0, 1, 3))(
        base.frozen(m), base.frozen(init), layer_key(seed, l), l)


def swiglu(h, w, prefix: str, quant=None):
    g = base.mm(h, w[f"{prefix}gate"], quant)
    u = base.mm(h, w[f"{prefix}up"], quant)
    return base.mm(jax.nn.silu(g) * u, w[f"{prefix}down"], quant)


def layer(x, w, pos, m: Dict, quant=None):
    """One block on one sequence, dense or MoE by its weights."""
    x = base.attn_block(x, w, pos, m, quant)
    h = base.rms_norm(x, w["ln2"], m["norm_eps"])
    if "router" in w:
        return x + base.moe(h, w, m, quant) + swiglu(h, w, "s_", quant)
    return x + swiglu(h, w, "w_", quant)


def layer_matmul_params(m: Dict, l: int) -> int:
    d, hd = m["d_model"], m["head_dim"]
    attn = d * (m["n_heads"] + 2 * m["n_kv_heads"]) * hd \
        + m["n_heads"] * hd * d
    if is_dense(m, l):
        return attn + 3 * d * m["d_ff"]
    return attn + d * m["num_experts"] \
        + (m["top_k"] + m["n_shared"]) * 3 * d * m["d_expert"]


def _matmul_flops(m: Dict) -> int:
    return 2 * sum(layer_matmul_params(m, l) for l in range(m["n_layers"]))


def token_flops(m: Dict, context: int) -> int:
    return _matmul_flops(m) + base.attn_flops(m, context) \
        + base.head_flops(m)


def prefill_flops(m: Dict, prompt_len: int) -> int:
    P = prompt_len
    return P * _matmul_flops(m) \
        + 4 * m["n_layers"] * m["n_heads"] * m["head_dim"] * P * (P + 1) // 2 \
        + base.head_flops(m)


make_top = base.make_top
