"""Jit'd public wrappers for the Pallas kernels.

On CPU (this container) the kernels execute in ``interpret=True`` mode —
the kernel body runs in Python for correctness validation; on TPU the same
``pallas_call`` compiles to Mosaic. ``interpret`` auto-detects the backend
unless forced via keyword.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
from jax import lax

from repro.kernels import flash_attention as _fa
from repro.kernels import fused_mlp as _fm
from repro.kernels import grouped_gemm as _gg
from repro.kernels import rmsnorm as _rn
from repro.kernels import topk_combine as _tc


def _interp(flag: Optional[bool]) -> bool:
    if flag is not None:
        return flag
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "order",
                                             "interpret"))
def grouped_gemm(lhs, rhs, bm: int = 128, bn: int = 128, bk: int = 512,
                 order: str = "expert_major", interpret: Optional[bool] = None):
    return _gg.grouped_gemm_padded(lhs, rhs, bm=bm, bn=bn, bk=bk, order=order,
                                   interpret=_interp(interpret))


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk",
                                             "interpret"))
def flash_attention(q, k, v, causal: bool = True, bq: int = 128,
                    bk: int = 128, interpret: Optional[bool] = None):
    return _fa.flash_attention(q, k, v, causal=causal, bq=bq, bk=bk,
                               interpret=_interp(interpret))


@functools.partial(jax.jit, static_argnames=("eps", "bt", "interpret"))
def rmsnorm(x, scale, eps: float = 1e-5, bt: int = 256,
            interpret: Optional[bool] = None):
    return _rn.rmsnorm(x, scale, eps=eps, bt=bt, interpret=_interp(interpret))


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def topk_combine(rows, weights, bt: int = _tc.DEFAULT_BT,
                 interpret: Optional[bool] = None):
    return _tc.topk_combine(rows, weights, bt=bt, interpret=_interp(interpret))


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def topk_combine_diff(rows, weights, bt: int = _tc.DEFAULT_BT,
                      interpret: Optional[bool] = None):
    """Differentiable combine kernel (custom_vjp) — what routing.combine
    calls inside the MoE layer."""
    return _tc.topk_combine_diff(rows, weights, bt=bt,
                                 interpret=_interp(interpret))


@functools.partial(jax.jit, static_argnames=("activation", "col_slice",
                                             "order", "bm", "bf", "bn",
                                             "interpret"))
def fused_mlp(rows, w, activation: str,
              col_slice: Optional[tuple] = None, order: str = "expert_major",
              bm: int = 128, bf: int = 512, bn: int = 0,
              interpret: Optional[bool] = None):
    """Fused GEMM1→activation→GEMM2 expert MLP (kernels/fused_mlp.py) — the
    ``"pallas_fused"`` GroupGEMM backend. ``w`` is the expert-weight dict
    (w_gate optional, w_up, w_down); ``col_slice=(start, width)`` computes
    only that output-column block (transport_comet's layer-1 decomposition),
    recomputing the hidden in VMEM instead of re-reading it from HBM."""
    return _fm.fused_mlp_padded(rows, w.get("w_gate"), w["w_up"],
                                _sliced_wd(w, col_slice),
                                activation=activation, bm=bm, bf=bf, bn=bn,
                                order=order, interpret=_interp(interpret))


def _sliced_wd(w, col_slice):
    wd = w["w_down"]
    if col_slice is not None:
        wd = lax.dynamic_slice_in_dim(wd, col_slice[0], col_slice[1], axis=2)
    return wd


@functools.partial(jax.jit, static_argnames=("activation", "col_slice",
                                             "bm", "bf", "interpret"))
def fused_mlp_dgrad(rows, w, dy, activation: str,
                    col_slice: Optional[tuple] = None,
                    bm: int = 128, bf: int = _fm.DGRAD_BF,
                    interpret: Optional[bool] = None):
    """Explicit dgrad of the fused expert MLP (kernels/fused_mlp.py):
    dX from a (possibly column-sliced) dY, hidden recomputed in VMEM.
    Per-block calls sum to the full dX (linearity in dY) — the comet
    backward ring's per-column-block dY consumption."""
    return _fm.fused_mlp_dgrad_padded(
        rows, w.get("w_gate"), w["w_up"], _sliced_wd(w, col_slice), dy,
        activation=activation, bm=bm, bf=bf, interpret=_interp(interpret))


@functools.partial(jax.jit, static_argnames=("activation", "col_slice",
                                             "bm", "bf", "interpret"))
def fused_mlp_wgrad(rows, w, dy, activation: str,
                    col_slice: Optional[tuple] = None,
                    bm: int = 128, bf: int = _fm.WGRAD_BF,
                    interpret: Optional[bool] = None):
    """Explicit wgrad of the fused expert MLP: (dw_gate|None, dw_up,
    dw_down). With ``col_slice`` the returned dw_down covers only that
    column block; dw_up/dw_gate are the block's partials (they sum over
    blocks to the full gradient)."""
    return _fm.fused_mlp_wgrad_padded(
        rows, w.get("w_gate"), w["w_up"], _sliced_wd(w, col_slice), dy,
        activation=activation, bm=bm, bf=bf, interpret=_interp(interpret))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_forward(x, dt, A, Bm, Cm, D, chunk: int = 64,
                interpret: Optional[bool] = None):
    from repro.kernels import ssd as _ssd
    return _ssd.ssd_forward(x, dt, A, Bm, Cm, D, chunk=chunk,
                            interpret=_interp(interpret))
