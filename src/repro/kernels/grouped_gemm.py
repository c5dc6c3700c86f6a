"""Grouped GEMM Pallas-TPU kernel with Comet traversal orders.

Computes ``out[e] = lhs[e] @ rhs[e]`` for all local experts e in ONE kernel
(the paper's GroupGEMM), with fp32 accumulation in VMEM scratch and MXU-sized
(128-multiple) tiles.

The Comet-specific feature is the **grid traversal order** (paper Fig. 6):

* ``order="expert_major"`` — grid (E, Mt, Nt, Kt): finish expert 0's whole
  output, then expert 1, … The combine for any output column can only start
  after the LAST expert finishes: no early tiles for the consumer.
* ``order="n_major"`` — grid (Nt, E, Mt, Kt): column-block 0 of EVERY expert
  completes first, so the layer-1 consumer (top-k reduce + return traffic) can
  start after a 1/Nt fraction of compute — exactly the paper's rescheduled
  column-major GroupGEMM. On real TPU the consumer is the async combine DMA;
  the traversal order controls *tile completion order*, which is what the
  overlap schedule keys on.

Grid iteration on TPU is sequential row-major over the grid tuple, so placing
N (resp. E) first is a faithful realization of the two schedules.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import COMPILER_PARAMS


def _gg_kernel(lhs_ref, rhs_ref, out_ref, acc_ref, *, nk: int):
    """One (bm, bn) tile of one expert; K-loop innermost via the grid."""
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(lhs_ref[0], rhs_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _flush():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def grouped_gemm(lhs: jnp.ndarray, rhs: jnp.ndarray, *,
                 bm: int = 128, bn: int = 128, bk: int = 512,
                 order: str = "expert_major",
                 out_dtype=None,
                 interpret: bool = False) -> jnp.ndarray:
    """lhs: (E, M, K); rhs: (E, K, N) -> (E, M, N).

    Block sizes are clamped to the problem and must divide it (callers pad);
    MXU alignment wants multiples of 128 on M/N and of 256 on K for bf16.
    """
    E, M, K = lhs.shape
    E2, K2, N = rhs.shape
    assert E == E2 and K == K2, (lhs.shape, rhs.shape)
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, \
        f"blocks ({bm},{bn},{bk}) must divide problem ({M},{N},{K})"
    mt, nt, kt = M // bm, N // bn, K // bk
    out_dtype = out_dtype or lhs.dtype

    if order == "expert_major":
        grid = (E, mt, nt, kt)
        lhs_map = lambda e, m, n, k: (e, m, k)
        rhs_map = lambda e, m, n, k: (e, k, n)
        out_map = lambda e, m, n, k: (e, m, n)
    elif order == "n_major":
        grid = (nt, E, mt, kt)
        lhs_map = lambda n, e, m, k: (e, m, k)
        rhs_map = lambda n, e, m, k: (e, k, n)
        out_map = lambda n, e, m, k: (e, m, n)
    else:
        raise ValueError(f"unknown order {order!r}")

    kernel = functools.partial(_gg_kernel, nk=kt)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lhs_map),
            pl.BlockSpec((1, bk, bn), rhs_map),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), out_map),
        out_shape=jax.ShapeDtypeStruct((E, M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(lhs, rhs)


def grouped_gemm_padded(lhs, rhs, *, bm=128, bn=128, bk=512,
                        order="expert_major", out_dtype=None,
                        interpret=False):
    """Pads M/N/K up to block multiples, runs the kernel, slices back."""
    E, M, K = lhs.shape
    N = rhs.shape[-1]
    pad = lambda x, b: (b - x % b) % b
    bm_, bn_, bk_ = min(bm, max(M, 1)), min(bn, max(N, 1)), min(bk, max(K, 1))
    pm, pn, pk = pad(M, bm_), pad(N, bn_), pad(K, bk_)
    if pm or pk:
        lhs = jnp.pad(lhs, ((0, 0), (0, pm), (0, pk)))
    if pk or pn:
        rhs = jnp.pad(rhs, ((0, 0), (0, pk), (0, pn)))
    out = grouped_gemm(lhs, rhs, bm=bm_, bn=bn_, bk=bk_, order=order,
                       out_dtype=out_dtype, interpret=interpret)
    return out[:, :M, :N]
