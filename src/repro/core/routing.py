"""Routing + shared-tensor construction (the paper's §3.1 substrate).

The *shared tensor* between dispatch (producer) and expert GEMM (consumer) is
the ``(E, C, d)`` dispatch buffer: decomposed along the token dim **M** into
per-destination-group chunks (layer 0), and along the hidden dim **N** into
column blocks (layer 1). All transports (naive / coarse / comet / bcast) use
*identical* routing, capacity and slot assignment so their outputs are
numerically identical — the equivalence tests rely on this.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


@dataclass
class DispatchInfo:
    flat_e: jnp.ndarray      # (T*k,) expert id per (token, choice)
    pos: jnp.ndarray         # (T*k,) slot within expert queue
    keep: jnp.ndarray        # (T*k,) bool, False = dropped by capacity
    T: int
    k: int


def capacity(T: int, k: int, E: int, factor: float, multiple: int = 4) -> int:
    c = math.ceil(T * k / E * factor)
    c = max(multiple, multiple * math.ceil(c / multiple))
    return c


@jax.named_scope("moe.router")
def router(x, w_router, mcfg, token_axes=()):
    """x: (T, d). Returns (idx (T,k), weights (T,k), aux_loss scalar fp32).

    token_axes: mesh axis names over which tokens are sharded; the Switch
    load-balance statistics (me, ce) are psum-averaged over them *before*
    taking the product, so the aux loss is identical under any sharding.
    """
    logits = x.astype(jnp.float32) @ w_router.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, mcfg.top_k)
    if mcfg.router_norm_topk:
        w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    E = logits.shape[-1]
    # Switch-style load-balance loss
    me = jnp.mean(probs, axis=0)                                   # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    ce = ce / jnp.maximum(idx.size, 1)
    if token_axes:
        me = jax.lax.pmean(me, token_axes)
        ce = jax.lax.pmean(ce, token_axes)
    aux = E * jnp.sum(me * ce) * mcfg.aux_loss_coef
    return idx, w, aux


@jax.named_scope("moe.dispatch")
def build_dispatch(x, idx, E: int, C: int) -> Tuple[jnp.ndarray, DispatchInfo]:
    """x: (T, d); idx: (T, k). Builds the shared tensor (E, C, d) with tokens
    sorted by (expert, arrival order) — slot = position in expert queue.

    Sort-based slot assignment: ranks come from an argsort over the composite
    key ``expert_id * T*k + arrival``, so the rank-in-queue of a (token,
    choice) is its position in the sorted order minus its expert's segment
    offset — O(T·k·log(T·k)) work instead of the O(T·k·E) one-hot cumsum.
    The buffer is then filled by ONE (E*C, d) gather through the inverse
    slot→token map; the (T*k, d) ``jnp.repeat`` copy of all activations the
    one-hot path needed is never materialized. Bit-identical to the one-hot
    reference (tests/test_fused_pipeline.py checks exactness)."""
    T, k = idx.shape
    d = x.shape[-1]
    TK = T * k
    flat_e = idx.reshape(-1).astype(jnp.int32)                     # (T*k,)
    # jnp.argsort is stable (lax.sort is_stable), so equal expert ids keep
    # arrival order — no composite key needed (one would overflow int32 at
    # E*T*k >= 2^31)
    order = jnp.argsort(flat_e)                                    # (T*k,)
    counts = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts)[:-1]])            # (E,)
    rank_sorted = jnp.arange(TK, dtype=jnp.int32) - starts[flat_e[order]]
    pos = jnp.zeros((TK,), jnp.int32).at[order].set(rank_sorted)   # (T*k,)
    keep = pos < C
    slot = jnp.where(keep, flat_e * C + jnp.minimum(pos, C - 1), E * C)
    # inverse map slot -> source token row; dropped (token, choice) pairs
    # scatter to the out-of-bounds slot E*C and vanish under mode="drop"
    tok = jnp.arange(TK, dtype=jnp.int32) // k
    src = jnp.zeros((E * C,), jnp.int32).at[slot].set(tok, mode="drop")
    filled = jnp.zeros((E * C,), jnp.bool_).at[slot].set(True, mode="drop")
    buf = jnp.where(filled[:, None], x[src], jnp.zeros((), x.dtype))
    return buf.reshape(E, C, d), DispatchInfo(flat_e, pos, keep, T, k)


@jax.named_scope("moe.combine")
def combine(recv_flat, info: DispatchInfo, weights, E_loc: int, C: int,
            rot: Optional[jnp.ndarray], ep: int) -> jnp.ndarray:
    """recv_flat: (ep*E_loc*C, d) expert outputs; slot layout (s, l, c) where
    chunk index s ↔ destination group g via ``g == s`` (naive; rot None) or
    ``s == (rot - g) % ep`` (comet ring rotation, rot = my group index).
    Returns (T, d) = top-k weighted sum, dropped slots contribute zero.

    The gather (slot → token rows) stays in XLA's gather engine; the fp32
    weighted reduction runs in the Pallas ``topk_combine`` kernel (the
    paper's layer-1 consumer), differentiable via its custom VJP — on TPU,
    or in interpret mode on CPU. Other backends (e.g. CUDA jax, where the
    Pallas TPU lowering does not exist) keep the pure-jnp reduction, same
    numerics. In the comet schedule ``d`` may be a single column block —
    the reduction is columnwise, so per-block combines concatenate to the
    full-width result."""
    g = info.flat_e // E_loc
    l = info.flat_e % E_loc
    s_idx = g if rot is None else (rot - g) % ep
    idx = (s_idx * E_loc + l) * C + jnp.minimum(info.pos, C - 1)
    rows = recv_flat[idx]                                          # (T*k, d)
    rows = jnp.where(info.keep[:, None], rows, 0)
    rows = rows.reshape(info.T, info.k, -1)
    if jax.default_backend() in ("cpu", "tpu"):
        from repro.kernels import ops
        return ops.topk_combine_diff(rows, weights)
    w = weights.astype(jnp.float32)[..., None]
    return jnp.sum(rows.astype(jnp.float32) * w, axis=1).astype(recv_flat.dtype)


def moe_flops(T: int, k: int, d: int, f: int, glu: bool) -> int:
    """Active FLOPs of one MoE FFN on T tokens (for roofline / adaptive)."""
    n_mat = 3 if glu else 2
    return 2 * T * k * n_mat * d * f
