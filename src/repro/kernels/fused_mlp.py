"""Fused expert-MLP Pallas-TPU kernel: GEMM1 → activation → GEMM2 in ONE
``pallas_call`` — the per-tile hidden activations live only in VMEM.

The unfused pipeline (``transport.expert_gemm1`` + ``expert_gemm2``)
materializes the hidden tensor ``h`` of shape (E_loc, R, f_loc) in HBM
between the two GroupGEMMs, and every N-decomposed GEMM2 column-block call
re-reads all of it. This kernel eliminates that round trip entirely: for
each (expert, row-tile, column-tile) output tile it streams f-chunks of the
expert weights through VMEM, computes the corresponding hidden chunk
``act(x @ w_gate[:, fc], x @ w_up[:, fc])`` on the fly, and accumulates
``h_chunk @ w_down[fc, :]`` into an fp32 VMEM accumulator. ``h`` never has
an HBM address.

Traversal orders mirror ``grouped_gemm.py`` (paper Fig. 6):

* ``order="expert_major"`` — grid (E, Mt, Nt, Ft): expert 0's output
  finishes first.
* ``order="n_major"``     — grid (Nt, E, Mt, Ft): column-block 0 of EVERY
  expert completes first, so the layer-1 consumer (combine + return
  traffic) can start after a 1/Nt fraction of the output.

Column-sliced calls (``transport_comet``'s N-decomposed early return) pass
a pre-sliced ``w_down`` — each per-block call recomputes its GEMM1 chunks
instead of re-reading an HBM-resident ``h``; the adaptive cost model
(``core/adaptive.py``) weighs exactly this recompute-vs-traffic trade.

VMEM budget per grid step: x tile (bm, d) + w_gate/w_up chunks (d, bf) +
w_down chunk (bf, bn) + fp32 accumulator (bm, bn). The d (d_model)
contraction is NOT chunked — callers with d ≳ 8k should shrink bf/bn.
The backward kernels hold more per f-chunk (wgrad keeps three fp32 dW
accumulators), so they default to narrower chunks, ``DGRAD_BF`` and
``WGRAD_BF``: with those, every kernel here compiles under
``VMEM_LIMIT_BYTES`` up to d_model 4096 (tests/test_tpu_compile.py).

Gradients: ``pallas_call`` has no automatic VJP, so ``fused_mlp_padded``
carries a ``jax.custom_vjp``. Its backward runs the explicit dgrad/wgrad
kernels below (PR 3): both rematerialize the hidden chunk in VMEM exactly
like the forward (``h`` never gets an HBM address in either direction), and
both accept a pre-sliced ``w_down``/``dy`` so the comet backward ring can
consume the dcombine stream per column block (the layer-1 N-decomposition
applied to the backward). ``fused_mlp_dgrad`` accumulates
``dX = dgate·w_gateᵀ + dup·w_upᵀ`` over f-chunks; ``fused_mlp_wgrad``
accumulates ``dW`` over row tiles, flushing per f-chunk output blocks. The
pure-jnp oracle (``kernels/ref.fused_mlp_ref``) remains the numerics
reference the tests compare both against.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import COMPILER_PARAMS
from repro.models.common import activate

# f-chunk widths of the backward kernels (the forward keeps bf=512). At
# bf=512 wgrad needs 38 MiB of VMEM at qwen2-moe-2.7b width (d 2048).
DGRAD_BF = 256
WGRAD_BF = 128


def _fused_kernel(*refs, nf: int, activation: str, glu: bool, n_pos: int):
    """One (bm, bn) output tile of one expert; F-chunk loop via the grid
    (innermost dim). ``n_pos`` is the grid position of the N index (2 for
    expert_major, 0 for n_major) — unused in the body but documents that the
    F axis (position 3) is the only accumulation axis."""
    del n_pos
    if glu:
        x_ref, wg_ref, wu_ref, wd_ref, out_ref, acc_ref = refs
    else:
        x_ref, wu_ref, wd_ref, out_ref, acc_ref = refs
        wg_ref = None
    fi = pl.program_id(3)

    @pl.when(fi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0]                                            # (bm, d)
    up = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
    if glu:
        gate = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        h = activate(activation, gate, up)                  # (bm, bf) fp32
    else:
        h = activate(activation, None, up)
    # match the unfused pipeline, which materializes h in the input dtype
    h = h.astype(x_ref.dtype)
    acc_ref[...] += jnp.dot(h, wd_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(fi == nf - 1)
    def _flush():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def fused_mlp(rows, w_gate, w_up, w_down, *, activation: str,
              bm: int = 128, bf: int = 512, bn: int = 0,
              order: str = "expert_major", out_dtype=None,
              interpret: bool = False) -> jnp.ndarray:
    """rows: (E, R, d); w_gate/w_up: (E, d, f) (w_gate None for non-GLU);
    w_down: (E, f, N) -> (E, R, N). Block sizes must divide the problem
    (callers pad); ``bn == 0`` means one full-width N tile."""
    E, R, d = rows.shape
    f = w_up.shape[-1]
    N = w_down.shape[-1]
    glu = w_gate is not None
    bm, bf = min(bm, R), min(bf, f)
    bn = N if bn <= 0 else min(bn, N)
    assert R % bm == 0 and f % bf == 0 and N % bn == 0, \
        f"blocks ({bm},{bf},{bn}) must divide problem (R={R},f={f},N={N})"
    mt, nt, ft = R // bm, N // bn, f // bf
    out_dtype = out_dtype or rows.dtype

    if order == "expert_major":
        grid = (E, mt, nt, ft)
        ix = lambda e, m, n, fi: (e, m, 0)
        iw1 = lambda e, m, n, fi: (e, 0, fi)
        iwd = lambda e, m, n, fi: (e, fi, n)
        io = lambda e, m, n, fi: (e, m, n)
        n_pos = 2
    elif order == "n_major":
        grid = (nt, E, mt, ft)
        ix = lambda n, e, m, fi: (e, m, 0)
        iw1 = lambda n, e, m, fi: (e, 0, fi)
        iwd = lambda n, e, m, fi: (e, fi, n)
        io = lambda n, e, m, fi: (e, m, n)
        n_pos = 0
    else:
        raise ValueError(f"unknown order {order!r}")

    in_specs = [pl.BlockSpec((1, bm, d), ix)]
    args = [rows]
    if glu:
        in_specs.append(pl.BlockSpec((1, d, bf), iw1))
        args.append(w_gate)
    in_specs.append(pl.BlockSpec((1, d, bf), iw1))
    args.append(w_up)
    in_specs.append(pl.BlockSpec((1, bf, bn), iwd))
    args.append(w_down)

    kernel = functools.partial(_fused_kernel, nf=ft, activation=activation,
                               glu=glu, n_pos=n_pos)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bm, bn), io),
        out_shape=jax.ShapeDtypeStruct((E, R, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(*args)


def _fused_mlp_run(rows, w_gate, w_up, w_down, *, activation, bm, bf, bn,
                   order, interpret):
    """Pads R/f/N up to block multiples, runs the kernel, slices back.
    Zero-padding is exact: padded x rows give zero outputs (sliced off), and
    padded f columns contribute ``h_pad @ 0`` because w_down's padded rows
    are zero."""
    E, R, d = rows.shape
    f = w_up.shape[-1]
    N = w_down.shape[-1]
    pad = lambda x, b: (b - x % b) % b
    bm_, bf_ = min(bm, max(R, 1)), min(bf, max(f, 1))
    bn_ = N if bn <= 0 else min(bn, N)
    pr, pf, pn = pad(R, bm_), pad(f, bf_), pad(N, bn_)
    if pr:
        rows = jnp.pad(rows, ((0, 0), (0, pr), (0, 0)))
    if pf:
        w_up = jnp.pad(w_up, ((0, 0), (0, 0), (0, pf)))
        if w_gate is not None:
            w_gate = jnp.pad(w_gate, ((0, 0), (0, 0), (0, pf)))
    if pf or pn:
        w_down = jnp.pad(w_down, ((0, 0), (0, pf), (0, pn)))
    out = fused_mlp(rows, w_gate, w_up, w_down, activation=activation,
                    bm=bm_, bf=bf_, bn=bn_, order=order, interpret=interpret)
    return out[:, :R, :N]


# ---------------------------------------------------------------------------
# Backward kernels: explicit dgrad / wgrad entry points (PR 3)
# ---------------------------------------------------------------------------


def _act_vjp(activation: str, glu: bool, gate, up, dh):
    """(dgate, dup) for h = activate(gate, up) given cotangent dh — traced
    jnp math, so it lowers inside the Pallas kernel body."""
    if glu:
        _, vjp = jax.vjp(lambda g, u: activate(activation, g, u), gate, up)
        return vjp(dh)
    _, vjp = jax.vjp(lambda u: activate(activation, None, u), up)
    return None, vjp(dh)[0]


def _dgrad_kernel(*refs, nf: int, activation: str, glu: bool):
    """One (bm, d) dX tile of one expert; f-chunk loop via the grid: each
    chunk recomputes its hidden slice in VMEM (gate/up from x), pulls its
    dh slice out of dY through w_downᵀ, and accumulates both layer-0
    transposed GEMMs into the fp32 dX accumulator."""
    if glu:
        x_ref, wg_ref, wu_ref, wd_ref, dy_ref, dx_ref, acc_ref = refs
    else:
        x_ref, wu_ref, wd_ref, dy_ref, dx_ref, acc_ref = refs
        wg_ref = None
    fi = pl.program_id(2)

    @pl.when(fi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0]                                            # (bm, d)
    dy = dy_ref[0]                                          # (bm, N)
    up = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
    gate = (jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
            if glu else None)
    dh = jnp.dot(dy, wd_ref[0].T, preferred_element_type=jnp.float32)
    # the forward casts h to the input dtype before GEMM2; mirror it so the
    # cotangent enters the activation VJP at matching precision
    dh = dh.astype(x_ref.dtype)
    dgate, dup = _act_vjp(activation, glu, gate, up, dh.astype(jnp.float32))
    acc_ref[...] += jnp.dot(dup.astype(x_ref.dtype), wu_ref[0].T,
                            preferred_element_type=jnp.float32)
    if glu:
        acc_ref[...] += jnp.dot(dgate.astype(x_ref.dtype), wg_ref[0].T,
                                preferred_element_type=jnp.float32)

    @pl.when(fi == nf - 1)
    def _flush():
        dx_ref[0] = acc_ref[...].astype(dx_ref.dtype)


def fused_mlp_dgrad(rows, w_gate, w_up, w_down, dy, *, activation: str,
                    bm: int = 128, bf: int = DGRAD_BF,
                    interpret: bool = False) -> jnp.ndarray:
    """rows: (E, R, d); w_down: (E, f, N); dy: (E, R, N) -> dX (E, R, d).
    ``w_down``/``dy`` may be a column block of the full output (the comet
    backward ring's per-block dY consumption). Block sizes must divide the
    problem (callers pad); d and N are not chunked."""
    E, R, d = rows.shape
    f = w_up.shape[-1]
    N = w_down.shape[-1]
    glu = w_gate is not None
    bm, bf = min(bm, R), min(bf, f)
    assert R % bm == 0 and f % bf == 0, \
        f"blocks ({bm},{bf}) must divide problem (R={R},f={f})"
    mt, ft = R // bm, f // bf

    grid = (E, mt, ft)
    ix = lambda e, m, fi: (e, m, 0)
    iw1 = lambda e, m, fi: (e, 0, fi)
    iwd = lambda e, m, fi: (e, fi, 0)
    idy = lambda e, m, fi: (e, m, 0)

    in_specs = [pl.BlockSpec((1, bm, d), ix)]
    args = [rows]
    if glu:
        in_specs.append(pl.BlockSpec((1, d, bf), iw1))
        args.append(w_gate)
    in_specs.append(pl.BlockSpec((1, d, bf), iw1))
    args.append(w_up)
    in_specs.append(pl.BlockSpec((1, bf, N), iwd))
    args.append(w_down)
    in_specs.append(pl.BlockSpec((1, bm, N), idy))
    args.append(dy)

    kernel = functools.partial(_dgrad_kernel, nf=ft, activation=activation,
                               glu=glu)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bm, d), ix),
        out_shape=jax.ShapeDtypeStruct((E, R, d), rows.dtype),
        scratch_shapes=[pltpu.VMEM((bm, d), jnp.float32)],
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(*args)


def _wgrad_kernel(*refs, nm: int, activation: str, glu: bool):
    """One f-chunk of all three dW outputs for one expert; row-tile loop via
    the grid (innermost). Recomputes the hidden chunk in VMEM, consumes the
    dY tile into dw_down = hᵀ·dY and (through the activation VJP) into
    dw_up/dw_gate = xᵀ·d{up,gate}, accumulating fp32 until the last tile."""
    if glu:
        (x_ref, wg_ref, wu_ref, wd_ref, dy_ref,
         dwg_ref, dwu_ref, dwd_ref, accg_ref, accu_ref, accd_ref) = refs
    else:
        (x_ref, wu_ref, wd_ref, dy_ref,
         dwu_ref, dwd_ref, accu_ref, accd_ref) = refs
        wg_ref = dwg_ref = accg_ref = None
    mi = pl.program_id(2)

    @pl.when(mi == 0)
    def _init():
        accu_ref[...] = jnp.zeros_like(accu_ref)
        accd_ref[...] = jnp.zeros_like(accd_ref)
        if glu:
            accg_ref[...] = jnp.zeros_like(accg_ref)

    x = x_ref[0]                                            # (bm, d)
    dy = dy_ref[0]                                          # (bm, N)
    up = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
    gate = (jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
            if glu else None)
    h = activate(activation, gate, up) if glu \
        else activate(activation, None, up)
    h = h.astype(x_ref.dtype)                               # matches forward
    accd_ref[...] += jnp.dot(h.T, dy, preferred_element_type=jnp.float32)
    dh = jnp.dot(dy, wd_ref[0].T, preferred_element_type=jnp.float32)
    dh = dh.astype(x_ref.dtype)
    dgate, dup = _act_vjp(activation, glu, gate, up, dh.astype(jnp.float32))
    accu_ref[...] += jnp.dot(x.T, dup.astype(x_ref.dtype),
                             preferred_element_type=jnp.float32)
    if glu:
        accg_ref[...] += jnp.dot(x.T, dgate.astype(x_ref.dtype),
                                 preferred_element_type=jnp.float32)

    @pl.when(mi == nm - 1)
    def _flush():
        dwu_ref[0] = accu_ref[...].astype(dwu_ref.dtype)
        dwd_ref[0] = accd_ref[...].astype(dwd_ref.dtype)
        if glu:
            dwg_ref[0] = accg_ref[...].astype(dwg_ref.dtype)


def fused_mlp_wgrad(rows, w_gate, w_up, w_down, dy, *, activation: str,
                    bm: int = 128, bf: int = WGRAD_BF,
                    interpret: bool = False):
    """rows: (E, R, d); dy: (E, R, N) -> (dw_gate | None, dw_up, dw_down)
    with dw_gate/dw_up: (E, d, f) and dw_down: (E, f, N). A column-sliced
    call (pre-sliced w_down/dy) yields the matching dw_down column block
    and the full-width dw_up/dw_gate PARTIALS for that block — the per-
    column-block contributions sum to the full wgrad (linearity in dY)."""
    E, R, d = rows.shape
    f = w_up.shape[-1]
    N = w_down.shape[-1]
    glu = w_gate is not None
    bm, bf = min(bm, R), min(bf, f)
    assert R % bm == 0 and f % bf == 0, \
        f"blocks ({bm},{bf}) must divide problem (R={R},f={f})"
    mt, ft = R // bm, f // bf

    grid = (E, ft, mt)
    ix = lambda e, fi, m: (e, m, 0)
    iw1 = lambda e, fi, m: (e, 0, fi)
    iwd = lambda e, fi, m: (e, fi, 0)
    idy = lambda e, fi, m: (e, m, 0)

    in_specs = [pl.BlockSpec((1, bm, d), ix)]
    args = [rows]
    if glu:
        in_specs.append(pl.BlockSpec((1, d, bf), iw1))
        args.append(w_gate)
    in_specs.append(pl.BlockSpec((1, d, bf), iw1))
    args.append(w_up)
    in_specs.append(pl.BlockSpec((1, bf, N), iwd))
    args.append(w_down)
    in_specs.append(pl.BlockSpec((1, bm, N), idy))
    args.append(dy)

    out_specs = []
    out_shapes = []
    if glu:
        out_specs.append(pl.BlockSpec((1, d, bf), iw1))
        out_shapes.append(jax.ShapeDtypeStruct((E, d, f), w_gate.dtype))
    out_specs.append(pl.BlockSpec((1, d, bf), iw1))
    out_shapes.append(jax.ShapeDtypeStruct((E, d, f), w_up.dtype))
    out_specs.append(pl.BlockSpec((1, bf, N), iwd))
    out_shapes.append(jax.ShapeDtypeStruct((E, f, N), w_down.dtype))

    scratch = []
    if glu:
        scratch.append(pltpu.VMEM((d, bf), jnp.float32))
    scratch.append(pltpu.VMEM((d, bf), jnp.float32))
    scratch.append(pltpu.VMEM((bf, N), jnp.float32))

    kernel = functools.partial(_wgrad_kernel, nm=mt, activation=activation,
                               glu=glu)
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=scratch,
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(*args)
    if glu:
        return outs[0], outs[1], outs[2]
    return None, outs[0], outs[1]


def _pad_bwd_args(rows, w_gate, w_up, w_down, dy, bm, bf):
    """Shared zero-padding for the backward kernels (R up to bm, f up to
    bf). Exact: padded rows/f-columns contribute zero to every grad (the
    padded weights are zero, and act(0)·0 chains vanish)."""
    E, R, d = rows.shape
    f = w_up.shape[-1]
    pad = lambda x, b: (b - x % b) % b
    bm_, bf_ = min(bm, max(R, 1)), min(bf, max(f, 1))
    pr, pf = pad(R, bm_), pad(f, bf_)
    if pr:
        rows = jnp.pad(rows, ((0, 0), (0, pr), (0, 0)))
        dy = jnp.pad(dy, ((0, 0), (0, pr), (0, 0)))
    if pf:
        w_up = jnp.pad(w_up, ((0, 0), (0, 0), (0, pf)))
        if w_gate is not None:
            w_gate = jnp.pad(w_gate, ((0, 0), (0, 0), (0, pf)))
        w_down = jnp.pad(w_down, ((0, 0), (0, pf), (0, 0)))
    return rows, w_gate, w_up, w_down, dy, bm_, bf_, R, f


def fused_mlp_dgrad_padded(rows, w_gate, w_up, w_down, dy, *,
                           activation: str, bm: int = 128,
                           bf: int = DGRAD_BF,
                           interpret: bool = False) -> jnp.ndarray:
    rows, w_gate, w_up, w_down, dy, bm_, bf_, R, _ = _pad_bwd_args(
        rows, w_gate, w_up, w_down, dy, bm, bf)
    dx = fused_mlp_dgrad(rows, w_gate, w_up, w_down, dy,
                         activation=activation, bm=bm_, bf=bf_,
                         interpret=interpret)
    return dx[:, :R, :]


def fused_mlp_wgrad_padded(rows, w_gate, w_up, w_down, dy, *,
                           activation: str, bm: int = 128,
                           bf: int = WGRAD_BF,
                           interpret: bool = False):
    rows, w_gate, w_up, w_down, dy, bm_, bf_, _, f = _pad_bwd_args(
        rows, w_gate, w_up, w_down, dy, bm, bf)
    dwg, dwu, dwd = fused_mlp_wgrad(rows, w_gate, w_up, w_down, dy,
                                    activation=activation, bm=bm_, bf=bf_,
                                    interpret=interpret)
    if dwg is not None:
        dwg = dwg[:, :, :f]
    return dwg, dwu[:, :, :f], dwd[:, :f, :]


@functools.lru_cache(maxsize=None)
def _diff_fused(activation: str, bm: int, bf: int, bn: int, order: str,
                interpret: bool):
    """custom_vjp closure per static config: forward = fused Pallas kernel,
    backward = the explicit dgrad + wgrad kernels (hidden rematerialized in
    VMEM both ways)."""

    @jax.custom_vjp
    def f(rows, w_gate, w_up, w_down):
        return _fused_mlp_run(rows, w_gate, w_up, w_down,
                              activation=activation, bm=bm, bf=bf, bn=bn,
                              order=order, interpret=interpret)

    def fwd(rows, w_gate, w_up, w_down):
        return f(rows, w_gate, w_up, w_down), (rows, w_gate, w_up, w_down)

    def bwd(res, ct):
        rows, w_gate, w_up, w_down = res
        ct = ct.astype(rows.dtype)
        dx = fused_mlp_dgrad_padded(rows, w_gate, w_up, w_down, ct,
                                    activation=activation, bm=bm,
                                    interpret=interpret)
        dwg, dwu, dwd = fused_mlp_wgrad_padded(rows, w_gate, w_up, w_down,
                                               ct, activation=activation,
                                               bm=bm, interpret=interpret)
        return dx, dwg, dwu, dwd

    f.defvjp(fwd, bwd)
    return f


def fused_mlp_padded(rows, w_gate, w_up, w_down, *, activation: str,
                     bm: int = 128, bf: int = 512, bn: int = 0,
                     order: str = "expert_major",
                     interpret: bool = False) -> jnp.ndarray:
    """Differentiable padded entry point (see module docstring)."""
    fn = _diff_fused(activation, bm, bf, bn, order, bool(interpret))
    return fn(rows, w_gate, w_up, w_down)
