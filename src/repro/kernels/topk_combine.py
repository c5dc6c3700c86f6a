"""Top-k combine epilogue as a Pallas-TPU kernel.

The paper's layer-1 consumer: after the N-major GroupGEMM produces expert
outputs, each token's k expert rows are weighted-summed in fp32. On TPU the
*gather* (slot → token) stays outside the kernel (dynamic HBM gathers belong
to XLA's gather engine, not VMEM tiles — hardware-adaptation note in
DESIGN.md); the kernel fuses the (T, k, d) weighted reduction, which is the
bandwidth-bound part that runs per column block in the overlap schedule.

The kernel reads ``rows`` as the free row-major view (T, k·d) and sums k
lane-aligned (bt, d) slices, each scaled by its (bt, 1) weight column, in
fp32. Mosaic lowers this; it refuses the equivalent
``einsum("tkd,tk->td")``, whose rhs non-contracting dim is not a suffix.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import COMPILER_PARAMS

# 128 rows keep the double-buffered (bt, k·d) bf16 block plus the fp32
# accumulator at 7.5 MiB for k=8, d=1536 (granite), well inside VMEM
DEFAULT_BT = 128


def _combine_kernel(rows_ref, w_ref, o_ref, *, k: int, d: int):
    w = w_ref[...].astype(jnp.float32)                # (bt, k)
    acc = rows_ref[:, 0:d].astype(jnp.float32) * w[:, 0:1]
    for j in range(1, k):
        acc += rows_ref[:, j * d:(j + 1) * d].astype(jnp.float32) \
            * w[:, j:j + 1]
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.lru_cache(maxsize=None)
def _diff_combine(bt: int, interpret: bool):
    """custom_vjp closure (``pallas_call`` has no automatic VJP): forward =
    kernel, backward = the analytic fp32 gradients of the weighted sum —
    routing.combine differentiates through this inside the MoE layer."""

    @jax.custom_vjp
    def f(rows, weights):
        return topk_combine(rows, weights, bt=bt, interpret=interpret)

    def fwd(rows, weights):
        return f(rows, weights), (rows, weights)

    def bwd(res, ct):
        rows, weights = res
        g = ct.astype(jnp.float32)[:, None, :]                # (T, 1, d)
        d_rows = (weights.astype(jnp.float32)[..., None] * g
                  ).astype(rows.dtype)                        # (T, k, d)
        d_w = jnp.sum(rows.astype(jnp.float32) * g, axis=-1
                      ).astype(weights.dtype)                 # (T, k)
        return d_rows, d_w

    f.defvjp(fwd, bwd)
    return f


def topk_combine_diff(rows, weights, *, bt: int = DEFAULT_BT,
                      interpret: bool = False):
    """Differentiable entry point for the combine kernel."""
    return _diff_combine(bt, bool(interpret))(rows, weights)


def topk_combine(rows: jnp.ndarray, weights: jnp.ndarray, *,
                 bt: int = DEFAULT_BT, interpret: bool = False
                 ) -> jnp.ndarray:
    """rows: (T, k, d) expert outputs per (token, choice); weights: (T, k).
    Returns (T, d) fp32-accumulated weighted sum, cast to rows.dtype."""
    T, k, d = rows.shape
    bt = min(bt, T)
    pad = (bt - T % bt) % bt
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0), (0, 0)))
        weights = jnp.pad(weights, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_combine_kernel, k=k, d=d),
        grid=((T + pad) // bt,),
        in_specs=[
            pl.BlockSpec((bt, k * d), lambda i: (i, 0)),
            pl.BlockSpec((bt, k), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bt, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((T + pad, d), rows.dtype),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(rows.reshape(T + pad, k * d), weights)
    return out[:T] if pad else out
