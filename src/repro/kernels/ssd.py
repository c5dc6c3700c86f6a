"""Mamba-2 SSD (state-space duality) forward as a Pallas-TPU kernel.

The XLA lowering of the chunked dual form materializes the (B, NC, Q, Q, nh)
decay tensor L and the per-chunk scan residuals in HBM — EXPERIMENTS.md §Perf
measured that traffic dominating mamba2's memory term. This kernel keeps the
whole intra-chunk working set (cum, L, CB, states) in VMEM:

  grid = (B*nh, NC)  — NC innermost; TPU grid iteration is sequential, so the
  inter-chunk recurrence h ← h·exp(total) + state carries through a VMEM
  scratch across the NC dimension exactly like flash attention's (m, l, acc).

Per (bh, c) step, everything is (Q, Q) / (Q, hd) / (ds, hd) tiles:
  cum   = cumsum(dt·A)                      (Q,)
  L     = tril(exp(cum_i - cum_j))          (Q, Q)    — never leaves VMEM
  CB    = C @ Bᵀ                            (Q, Q)
  y     = (CB ⊙ L) @ (x·dt)  +  exp(cum)·C @ h  +  D·x
  h    += exp(total - cum_j)·Bᵀ @ (x·dt)    (ds, hd)

HBM traffic is exactly the boundary: read x, dt, B, C once; write y once —
the roofline-model contract behind the ``__fusable__`` accounting.
The pure-jnp oracle is kernels/ref.py::ssd_ref (== models/ssm.ssd_reference).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import COMPILER_PARAMS


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref,
                h_ref, *, nc: int, Q: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0].astype(jnp.float32)                  # (Q, hd)
    dt = dt_ref[0].astype(jnp.float32)                # (Q, 1)
    A = a_ref[0, 0]                                   # scalar (this head)
    Bm = b_ref[0].astype(jnp.float32)                 # (Q, ds)
    Cm = c_ref[0].astype(jnp.float32)                 # (Q, ds)
    D = d_ref[0, 0]

    xd = x * dt                                       # discretized input
    la = dt[:, 0] * A                                 # (Q,) log-decay ≤ 0
    cum = jnp.cumsum(la)                              # (Q,)
    total = cum[-1]

    # intra-chunk: L[i,j] = exp(cum_i - cum_j) for i >= j, else 0
    diff = cum[:, None] - cum[None, :]                # (Q, Q)
    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    L = jnp.where(ii >= jj, jnp.exp(diff), 0.0)
    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())))  # (Q, Q)
    y = jnp.dot(CB * L, xd, preferred_element_type=jnp.float32)

    # inter-chunk: contribution of the carried state, then update it
    h = h_ref[...]                                    # (ds, hd) fp32
    y = y + jnp.exp(cum)[:, None] * jnp.dot(Cm, h,
                                            preferred_element_type=jnp.float32)
    decay_to_end = jnp.exp(total - cum)               # (Q,)
    h_ref[...] = h * jnp.exp(total) + jax.lax.dot_general(
        Bm * decay_to_end[:, None], xd, (((0,), (0,)), ((), ())))

    y_ref[0] = (y + D * x).astype(y_ref.dtype)


def ssd_forward(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
                Bm: jnp.ndarray, Cm: jnp.ndarray, D: jnp.ndarray, *,
                chunk: int = 64, interpret: bool = False) -> jnp.ndarray:
    """x: (B, S, nh, hd); dt: (B, S, nh); A/D: (nh,); Bm/Cm: (B, S, ds).
    Returns y: (B, S, nh, hd). S must be a multiple of ``chunk``."""
    Bsz, S, nh, hd = x.shape
    ds = Bm.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0, (S, Q)
    nc = S // Q

    # (B*nh, NC*Q, ·) layouts so the grid can be (B*nh, NC)
    xr = x.transpose(0, 2, 1, 3).reshape(Bsz * nh, S, hd)
    dtr = dt.transpose(0, 2, 1).reshape(Bsz * nh, S, 1)
    br = jnp.broadcast_to(Bm[:, None], (Bsz, nh, S, ds)).reshape(
        Bsz * nh, S, ds)
    cr = jnp.broadcast_to(Cm[:, None], (Bsz, nh, S, ds)).reshape(
        Bsz * nh, S, ds)
    ar = jnp.broadcast_to(A[None, :], (Bsz, nh)).reshape(Bsz * nh, 1)
    dr = jnp.broadcast_to(D[None, :], (Bsz, nh)).reshape(Bsz * nh, 1)

    kernel = functools.partial(_ssd_kernel, nc=nc, Q=Q)
    out = pl.pallas_call(
        kernel,
        grid=(Bsz * nh, nc),
        in_specs=[
            pl.BlockSpec((1, Q, hd), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, Q, 1), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, 1), lambda bh, c: (bh, 0)),
            pl.BlockSpec((1, Q, ds), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, Q, ds), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, 1), lambda bh, c: (bh, 0)),
        ],
        out_specs=pl.BlockSpec((1, Q, hd), lambda bh, c: (bh, c, 0)),
        out_shape=jax.ShapeDtypeStruct((Bsz * nh, S, hd), x.dtype),
        scratch_shapes=[pltpu.VMEM((ds, hd), jnp.float32)],   # carried state
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(xr, dtr, ar, br, cr, dr)
    return out.reshape(Bsz, nh, S, hd).transpose(0, 2, 1, 3)
