"""Plain reference of a decoder-only transformer with GQA attention (RoPE,
split halves) and a top-k mixture-of-experts MLP (SwiGLU experts, softmax
router, top-k weights renormalised), RMSNorm before each block, tied or
untied output head. This is the architecture of granite-3.0-3b-a800m as
the program implements it; the granite embedding, attention, residual and
logit multipliers are absent from both (the configuration lists them
under ``assumed``).

Everything here is straightforward ``jax.numpy`` in float32 at
``HIGHEST`` matmul precision, and imports nothing of the program. The MoE
is dropless: every token reaches all of its top-k experts (the program's
capacity-buffered dispatch may drop some; the configuration file says
how the comparison treats that).

The weights are made here too, from the seed, so that the program and the
reference get the same numbers without the reference reading anything the
program made: ``layer_weights(spec, seed, l)`` gives layer ``l`` alone, in
the canonical layout below, stored in the configuration's parameter dtype.

``quant="fp8"`` is the control: every matmul operand is rounded to
float8_e4m3fn with a per-tensor scale, the precision a later change might
be tempted to serve in.

The interface the harness calls (``bench/reference/__init__.py``):
``spec``, ``layer_key``/``top_key``, ``top_weights``/``layer_weights``,
``embed``/``layer``/``logits`` and ``token_flops``/``prefill_flops``.
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST

def spec(conf: Dict) -> "_frozen":
    """The reference's view of the model (its own key names), from the
    configuration file's published keys and what it states as run."""
    hf = conf
    return frozen({
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


# canonical layout of one layer's weights (names are the reference's own)
LAYER_KEYS = ("ln1", "wq", "wk", "wv", "wo", "ln2", "router",
              "w_gate", "w_up", "w_down")


def root_key(seed: int, purpose: str):
    """A PRNG key for one purpose of one seed (any whole number)."""
    ss = np.random.SeedSequence([int(seed) % 2**128,
                                 int.from_bytes(purpose.encode(), "little")])
    return jax.random.PRNGKey(int(ss.generate_state(1, np.uint32)[0]))


def layer_shapes(m: Dict) -> Dict[str, tuple]:
    d, E, f = m["d_model"], m["num_experts"], m["d_expert"]
    qd, kvd = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    return {"ln1": (d,), "wq": (d, qd), "wk": (d, kvd), "wv": (d, kvd),
            "wo": (qd, d), "ln2": (d,), "router": (d, E),
            "w_gate": (E, d, f), "w_up": (E, d, f), "w_down": (E, f, d)}


def _fan_in(name: str, shape: tuple) -> int:
    return shape[-2]


def make_layer(m: Dict, init: Dict, key) -> Dict[str, jnp.ndarray]:
    """One layer's weights from its key (jit- and vmap-able). Matrices are
    normal with std 1/sqrt(fan_in), stored in ``param_dtype``; norm scales
    are 1 + ``norm_jitter`` * normal, kept in float32."""
    shapes = layer_shapes(m)
    keys = jax.random.split(key, len(LAYER_KEYS))
    pdt = jnp.dtype(m["param_dtype"])
    out = {}
    for k, name in zip(keys, LAYER_KEYS):
        shape = shapes[name]
        z = jax.random.normal(k, shape, F32)
        if name.startswith("ln"):
            out[name] = 1.0 + init["norm_jitter"] * z
        else:
            out[name] = (z / math.sqrt(_fan_in(name, shape))).astype(pdt)
    return out


def make_top(m: Dict, init: Dict, key) -> Dict[str, jnp.ndarray]:
    """Embedding (and untied head) and the final norm scale."""
    k1, k2, k3 = jax.random.split(key, 3)
    pdt = jnp.dtype(m["param_dtype"])
    V, d = m["vocab_size"], m["d_model"]
    out = {"embed": (jax.random.normal(k1, (V, d), F32)
                     * init["embed_std"]).astype(pdt),
           "ln_f": 1.0 + init["norm_jitter"] * jax.random.normal(k2, (d,),
                                                                 F32)}
    if not m["tie_embeddings"]:
        out["lm_head"] = (jax.random.normal(k3, (d, V), F32)
                          / math.sqrt(d)).astype(pdt)
    return out


def layer_key(seed: int, l: int):
    return jax.random.fold_in(root_key(seed, "layers"), l)


def top_key(seed: int):
    return root_key(seed, "top")


def layer_weights(m: Dict, init: Dict, seed: int, l: int):
    return jax.jit(make_layer, static_argnums=(0, 1))(
        _frozen(m), _frozen(init), layer_key(seed, l))


def top_weights(m: Dict, init: Dict, seed: int):
    return jax.jit(make_top, static_argnums=(0, 1))(
        _frozen(m), _frozen(init), top_key(seed))


class _frozen(dict):
    """A dict that can be a static jit argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, repr(v)) for k, v in self.items())))


def frozen(d: Dict) -> "_frozen":
    return _frozen(d)


# ---------------------------------------------------------------------------
# The forward pass
# ---------------------------------------------------------------------------


def _round(x, dtype, top: float):
    """x rounded to a float8 ``dtype`` under a per-tensor absmax scale."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(dtype).astype(F32) * s


def _q(x, quant: Optional[str]):
    """Matmul operand in the reference's precision: float32, or the fp8
    control: rounded to float8_e4m3fn under a per-tensor absmax scale."""
    x = x.astype(F32)
    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError(f"unknown control precision {quant!r}")
    return _round(x, jnp.float8_e4m3fn, 448.0)


def mm(a, b, quant=None, eq: Optional[str] = None):
    if eq is None:
        return jnp.matmul(_q(a, quant), _q(b, quant), precision=HIGHEST)
    return jnp.einsum(eq, _q(a, quant), _q(b, quant), precision=HIGHEST)


def rms_norm(x, scale, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def rope(x, pos, theta):
    """x: (..., T, H, hd); pos: (..., T). Rotates the two halves."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos[..., None].astype(F32) * freqs
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_attention(q, k, v, q_block: int = 1024):
    """q: (T, H, hd); k, v: (T, Hkv, hd). Query head h reads kv head
    h // (H / Hkv). Exact softmax over each query's prefix, in query
    blocks (recomputed for the backward pass) so the score matrix stays
    small."""
    T, H, hd = q.shape
    rep = H // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    qb = min(q_block, T)
    pad = (-T) % qb
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, qb, H, hd)
    kpos = jnp.arange(T)

    @jax.checkpoint
    def block(args):
        i, qblk = args
        s = jnp.einsum("qhd,khd->hqk", qblk, k, precision=HIGHEST) \
            / math.sqrt(hd)
        qpos = i * qb + jnp.arange(qb)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s,
                      -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", w, v, precision=HIGHEST)

    out = jax.lax.map(block, (jnp.arange(qp.shape[0]), qp))
    return out.reshape(-1, H, hd)[:T]


def route(h, w, m: Dict, quant=None):
    """Router: softmax over the experts, top-k, weights renormalised.
    Returns (probs (N, E), top weights (N, k), top experts (N, k))."""
    probs = jax.nn.softmax(mm(h, w["router"], quant), axis=-1)
    topv, topi = jax.lax.top_k(probs, m["top_k"])
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    return probs, topv, topi



def moe(h, w, m: Dict, quant=None):
    """Dropless top-k MoE on h (N, d), every expert computed densely and
    weighted by the token's gate (0 off its top-k). Returns y (N, d)."""
    E = m["num_experts"]
    _, topv, topi = route(h, w, m, quant)
    N = h.shape[0]
    gates = jnp.zeros((N, E), F32).at[jnp.arange(N)[:, None], topi].set(topv)
    g = mm(h, w["w_gate"], quant, "td,edf->tef")
    u = mm(h, w["w_up"], quant, "td,edf->tef")
    a = jax.nn.silu(g) * u * gates[..., None]
    y = mm(a, w["w_down"], quant, "tef,efd->td")
    return y


def attn_block(x, w, pos, m: Dict, quant=None):
    """x + attention(rmsnorm(x)) on one sequence: x (T, d), pos (T,)."""
    T = x.shape[0]
    H, Hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    h = rms_norm(x, w["ln1"], m["norm_eps"])
    q = mm(h, w["wq"], quant).reshape(T, H, hd)
    k = mm(h, w["wk"], quant).reshape(T, Hkv, hd)
    v = mm(h, w["wv"], quant).reshape(T, Hkv, hd)
    q = rope(q, pos, m["rope_theta"])
    k = rope(k, pos, m["rope_theta"])
    o = causal_attention(q, k, v).reshape(T, H * hd)
    return x + mm(o, w["wo"], quant)


def layer(x, w, pos, m: Dict, quant=None):
    """One block on one sequence: x (T, d) float32, pos (T,)."""
    x = attn_block(x, w, pos, m, quant)
    return x + moe(rms_norm(x, w["ln2"], m["norm_eps"]), w, m, quant)



def embed(tokens, top: Dict, m: Dict):
    """Token ids (T,) -> hidden states (T, d) float32."""
    return jnp.take(top["embed"], tokens, axis=0).astype(F32)


def head_matrix(top: Dict):
    return top["embed"].T if "lm_head" not in top else top["lm_head"]


def logits(x, top: Dict, m: Dict, quant=None):
    h = rms_norm(x, top["ln_f"], m["norm_eps"])
    return mm(h, head_matrix(top), quant)


# ---------------------------------------------------------------------------
# Model FLOPs: what the algorithm requires, no recomputation, no capacity
# padding, no padded rows
# ---------------------------------------------------------------------------


def layer_matmul_params(m: Dict) -> int:
    """Weights one token multiplies by in one layer: attention
    projections, router and its top-k experts (SwiGLU: three matrices)."""
    d, hd = m["d_model"], m["head_dim"]
    attn = d * (m["n_heads"] + 2 * m["n_kv_heads"]) * hd \
        + m["n_heads"] * hd * d
    router = d * m["num_experts"]
    experts = m["top_k"] * 3 * d * m["d_expert"]
    return attn + router + experts


def head_flops(m: Dict) -> int:
    """One token's logits."""
    return 2 * m["d_model"] * m["vocab_size"]


def attn_flops(m: Dict, context: int) -> int:
    """One query attending over ``context`` keys in every layer (QK^T and
    PV)."""
    return 4 * m["n_layers"] * m["n_heads"] * m["head_dim"] * context


def token_flops(m: Dict, context: int, head: bool = True) -> int:
    """Forward FLOPs of one token at a context of ``context`` keys."""
    return 2 * m["n_layers"] * layer_matmul_params(m) \
        + attn_flops(m, context) + (head_flops(m) if head else 0)


def prefill_flops(m: Dict, prompt_len: int) -> int:
    """A prompt's prefill: every token through every layer with causal
    attention, logits for the last token only."""
    P = prompt_len
    return P * 2 * m["n_layers"] * layer_matmul_params(m) \
        + 4 * m["n_layers"] * m["n_heads"] * m["head_dim"] * P * (P + 1) // 2 \
        + head_flops(m)
