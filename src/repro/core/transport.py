"""MoE transports: how the shared tensor moves between ranks.

All functions take the dispatch buffer ``send`` of shape (ep, E_loc, C, d)
(chunked by destination expert-group — the paper's M-dimension decomposition)
and local expert weights, and return ``recv_out`` of shape (ep, E_loc, C, d)
holding this rank's tokens' expert outputs, plus the ring rotation needed by
``combine``.

  naive   — single all_to_all in, grouped MLP, single all_to_all back
            (Megatron-style non-overlapped baseline).
  coarse  — FasterMoE/Tutel-style: token range split into ``n`` slices, each
            slice runs the naive schedule; slices pipeline at kernel level.
            (Implemented at the layer level in moe_layer.py.)
  comet   — the paper: decomposed collectives. Dispatch is ep-1 ring steps of
            collective-permute; the chunk at ICI distance 0 (local) computes
            first (paper's "sort by source rank / local tiles first"), each
            chunk's expert MLP is fused GEMM1→act→GEMM2 and its *output is
            returned immediately* via a reverse permute — both directions
            overlap the next chunk's compute (XLA async collective-permute).
            Layer-1's N-dimension decomposition: the second GEMM produces
            ``n_col_blocks`` column blocks, each combined/returned as soon as
            it completes (paper Fig. 6 column-major GroupGEMM traversal).
  bcast   — decode-shape path: tokens replicated over the model axis, each
            rank computes its experts, psum combines. No dispatch collective.

ETP (> 1) shards every expert's hidden dim across ``etp`` adjacent ranks of
the model axis; chunks are replicated across the etp subgroup (collectives
use axis_index_groups), partial GEMM2 outputs psum over the subgroup.

Backward (PR 3): ``transport_comet_blocks`` carries a ``jax.custom_vjp``
that schedules the backward as its OWN decomposed ring instead of XLA's
transposed program (which serializes every reverse ppermute after the
forward completes). dY chunks travel the reverse permutes while the
previous chunk's dgrad GEMMs (w_downᵀ/w_upᵀ) and dW accumulation run, dX
chunks return along the transposed dispatch permutes, and the layer-1
N-decomposition applies to the dcombine stream: each column block's dY is
consumed (dh accumulation + per-column-block dw_down) as it arrives,
mirroring ``fused_combine``. Residuals: the fused backend saves only the
per-step dispatched rows — its explicit ``fused_mlp_dgrad``/
``fused_mlp_wgrad`` kernels rematerialize the hidden in VMEM; unfused
backends additionally save the layer-0 pre-activations (exactly what XLA
autodiff would save), so their backward spends no GEMM recompute.

The GroupGEMM backend is threaded EXPLICITLY (``gemm_impl=``) through every
entry point; a caller that does not choose gets the static ``"xla"``
default (``DEFAULT_GEMM_IMPL`` — a constant, not a mutable global).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.adaptive import (WIRE_DTYPES, hier_step_order,
                                 legalize_intra_group, legalize_n_col,
                                 legalize_ring_group)
from repro.models.common import activate, is_glu
from repro.parallel.mesh import AxisCtx


# ---------------------------------------------------------------------------
# Expert MLP (GroupGEMM over local experts)
# ---------------------------------------------------------------------------

# GroupGEMM backend:
#   "xla"          — einsum; XLA fuses + reorders freely.
#   "pallas"       — kernels/grouped_gemm.py with Comet traversal orders (on
#                    TPU this pins tile completion order; layer-1 uses
#                    order="n_major" per Fig. 6).
#   "pallas_fused" — kernels/fused_mlp.py: GEMM1→activation→GEMM2 in one
#                    kernel, hidden activations VMEM-resident (no
#                    (E_loc, R, f_loc) HBM round trip).
GEMM_BACKENDS = ("xla", "pallas", "pallas_fused")
DEFAULT_GEMM_IMPL = "xla"


def _impl(gemm_impl: Optional[str]) -> str:
    """Resolve a caller's backend choice; None/"" is the STATIC "xla"
    default — there is no mutable ambient global, the backend is always
    either explicit (MoEConfig.gemm_impl, set by Plan.apply) or "xla"."""
    if gemm_impl is None or gemm_impl == "":
        return DEFAULT_GEMM_IMPL
    assert gemm_impl in GEMM_BACKENDS, gemm_impl
    return gemm_impl


def _gg(rows, w, order="expert_major", gemm_impl: Optional[str] = None):
    if _impl(gemm_impl) == "pallas":
        from repro.kernels import ops
        return ops.grouped_gemm(rows, w, order=order)
    # one contraction covers both layouts — (E,R,d)@(E,d,f) and
    # (E,R,f)@(E,f,d) differ only in axis naming
    return jnp.einsum("erk,ekn->ern", rows, w)


def expert_gemm1(rows, w, activation: str, gemm_impl: Optional[str] = None):
    """rows: (E_loc, R, d) -> h: (E_loc, R, f_loc)."""
    if is_glu(activation):
        gate = _gg(rows, w["w_gate"], gemm_impl=gemm_impl)
        up = _gg(rows, w["w_up"], gemm_impl=gemm_impl)
        return activate(activation, gate, up)
    up = _gg(rows, w["w_up"], gemm_impl=gemm_impl)
    return activate(activation, None, up)


def expert_gemm2(h, w, col_slice: Optional[Tuple[int, int]] = None,
                 gemm_impl: Optional[str] = None):
    """h: (E_loc, R, f_loc) -> (E_loc, R, d_block)."""
    wd = w["w_down"]
    if col_slice is not None:
        wd = lax.dynamic_slice_in_dim(wd, col_slice[0], col_slice[1], axis=2)
    return _gg(h, wd, order="n_major", gemm_impl=gemm_impl)


@jax.named_scope("moe.experts")
def _mlp_out(rows, w, activation: str, gemm_impl: Optional[str] = None):
    """Full-width expert MLP under the chosen backend: one fused kernel call
    (hidden stays in VMEM) or the two-GEMM pipeline (hidden through HBM)."""
    if _impl(gemm_impl) == "pallas_fused":
        from repro.kernels import ops
        return ops.fused_mlp(rows, w, activation)
    return expert_gemm2(expert_gemm1(rows, w, activation, gemm_impl), w,
                        gemm_impl=gemm_impl)


@jax.named_scope("moe.experts")
def mlp_col_blocks(rows, w, activation: str, n_col: int, blk: int,
                   gemm_impl: Optional[str] = None):
    """Per-column-block expert MLP outputs — the layer-1 producer interface
    for the comet schedule. Returns a list of ``n_col`` arrays
    (E_loc, R, blk). Unfused backends share one HBM-resident hidden across
    the blocks (each GEMM2 call re-reads it); the fused backend issues one
    col-sliced kernel per block, recomputing the hidden in VMEM — the
    recompute-vs-HBM-traffic trade the adaptive cost model ranks."""
    if _impl(gemm_impl) == "pallas_fused":
        from repro.kernels import ops
        return [ops.fused_mlp(rows, w, activation, col_slice=(b * blk, blk),
                              order="n_major")
                for b in range(n_col)]
    h = expert_gemm1(rows, w, activation, gemm_impl)
    return [expert_gemm2(h, w, (b * blk, blk), gemm_impl)
            for b in range(n_col)]


def _mlp_preacts(rows, w, activation: str, gemm_impl: Optional[str] = None):
    """Layer-0 pre-activations (gate, up) — what the unfused forward ring
    SAVES for its backward (the same tensors XLA autodiff would save), so
    the backward spends no GEMM recompute. gate is None for non-GLU."""
    up = _gg(rows, w["w_up"], gemm_impl=gemm_impl)
    gate = (_gg(rows, w["w_gate"], gemm_impl=gemm_impl)
            if is_glu(activation) else None)
    return gate, up


def _mlp_bwd(rows, w, activation: str, dys, blk: int,
             gemm_impl: Optional[str] = None, preacts=None):
    """Per-chunk MLP backward with per-column-block dY consumption (the
    layer-1 N-decomposition applied to the dcombine stream).

    rows: (E_loc, R, d); dys: list of n_col column-block cotangents
    (E_loc, R, blk) partitioning the output width. Returns
    (d_rows (E_loc, R, d), dw dict matching ``w``'s keys).

    Fused backend: each block runs the explicit col-sliced dgrad/wgrad
    kernels (hidden recomputed in VMEM, matching the forward's
    ``col_slice``/``n_major`` traversal — the forward never materialized
    it); per-block dX / dw_up / dw_gate partials sum to the full gradients
    (linearity in dY). Unfused backends reuse the saved ``preacts``
    (recomputing them only when the caller saved nothing), stream the dY
    blocks into the dh accumulator and the per-block dw_down columns, then
    run one activation VJP and the transposed layer-0 GEMMs. The
    ``"pallas"`` backend shares this einsum backward with ``"xla"``: the
    grouped-GEMM kernel is a forward-layout kernel, and the transposed
    contractions here deliberately stay in XLA (identical numerics; only
    the forward's tile-completion order needed pinning)."""
    impl = _impl(gemm_impl)
    n_col = len(dys)
    glu = is_glu(activation)
    if impl == "pallas_fused":
        from repro.kernels import ops
        d_rows = None
        dwg = dwu = None
        dwd_blocks = []
        for b, dy in enumerate(dys):
            cs = (b * blk, blk) if n_col > 1 else None
            dx = ops.fused_mlp_dgrad(rows, w, dy, activation, col_slice=cs)
            g_, u_, d_ = ops.fused_mlp_wgrad(rows, w, dy, activation,
                                             col_slice=cs)
            d_rows = dx if d_rows is None else d_rows + dx
            dwu = u_ if dwu is None else dwu + u_
            if glu:
                dwg = g_ if dwg is None else dwg + g_
            dwd_blocks.append(d_)
        dwd = dwd_blocks[0] if n_col == 1 \
            else jnp.concatenate(dwd_blocks, axis=2)
        dw = {"w_up": dwu, "w_down": dwd}
        if glu:
            dw["w_gate"] = dwg
        return d_rows, dw

    if preacts is None:
        preacts = _mlp_preacts(rows, w, activation, impl)
    gate, up = preacts
    if glu:
        h, act_vjp = jax.vjp(lambda g, u: activate(activation, g, u),
                             gate, up)
    else:
        h, act_vjp = jax.vjp(lambda u: activate(activation, None, u), up)
    h_cast = h.astype(rows.dtype)       # the forward's pre-GEMM2 cast
    dh = None
    dwd_blocks = []
    for b, dy in enumerate(dys):
        wd_b = (lax.dynamic_slice_in_dim(w["w_down"], b * blk, blk, axis=2)
                if n_col > 1 else w["w_down"])
        dh_b = jnp.einsum("erb,efb->erf", dy, wd_b)
        dh = dh_b if dh is None else dh + dh_b
        dwd_blocks.append(jnp.einsum("erf,erb->efb", h_cast, dy))
    dwd = dwd_blocks[0] if n_col == 1 else jnp.concatenate(dwd_blocks, axis=2)
    dh = dh.astype(h.dtype)
    if glu:
        dgate, dup = act_vjp(dh)
        d_rows = (jnp.einsum("erf,edf->erd", dup, w["w_up"])
                  + jnp.einsum("erf,edf->erd", dgate, w["w_gate"]))
        dw = {"w_up": jnp.einsum("erd,erf->edf", rows, dup),
              "w_gate": jnp.einsum("erd,erf->edf", rows, dgate),
              "w_down": dwd}
    else:
        dup, = act_vjp(dh)
        d_rows = jnp.einsum("erf,edf->erd", dup, w["w_up"])
        dw = {"w_up": jnp.einsum("erd,erf->edf", rows, dup), "w_down": dwd}
    return d_rows.astype(rows.dtype), dw


def _cast_like(dw: Dict, w: Dict) -> Dict:
    return {k: dw[k].astype(w[k].dtype) for k in w}


def _etp_psum(ctx: AxisCtx, x):
    if ctx.etp == 1:
        return x
    return lax.psum(x, ctx.model_axis, axis_index_groups=ctx.etp_groups())


def expert_mlp(ctx: AxisCtx, rows, w, activation: str,
               gemm_impl: Optional[str] = None):
    return _etp_psum(ctx, _mlp_out(rows, w, activation, gemm_impl))


# ---------------------------------------------------------------------------
# naive: one all_to_all each way
# ---------------------------------------------------------------------------


def transport_naive(ctx: AxisCtx, send, w, activation: str,
                    gemm_impl: Optional[str] = None):
    ep, E_loc, C, d = send.shape
    ax = ctx.model_axis
    if not ctx.active or ctx.world == 1:
        rows = send.transpose(1, 0, 2, 3).reshape(E_loc, ep * C, d)
        out = expert_mlp(ctx, rows, w, activation, gemm_impl)
        out = out.reshape(E_loc, ep, C, d).transpose(1, 0, 2, 3)
        return out, None

    if ctx.etp == 1:
        recv = lax.all_to_all(send, ax, 0, 0, tiled=True)           # (ep,E_loc,C,d)
        rows = recv.transpose(1, 0, 2, 3).reshape(E_loc, ep * C, d)
        out = expert_mlp(ctx, rows, w, activation, gemm_impl)
        out = out.reshape(E_loc, ep, C, d).transpose(1, 0, 2, 3)
        ret = lax.all_to_all(out, ax, 0, 0, tiled=True)
        return ret, None

    # ETP > 1: replicate chunks across the etp subgroup, exchange within
    # same-tp groups, psum partials, return from the tp-matching rank.
    etp, ep_g = ctx.etp, ctx.ep
    gathered = lax.all_gather(send, ax, axis_index_groups=ctx.etp_groups())
    # (etp, ep, E_loc, C, d): gathered[t] = send buffer of subgroup member t
    recv = lax.all_to_all(gathered, ax, 1, 1, axis_index_groups=ctx.tp_groups(),
                          tiled=True)                               # (etp,ep,...)
    rows = recv.transpose(2, 0, 1, 3, 4).reshape(E_loc, etp * ep_g * C, d)
    out = expert_mlp(ctx, rows, w, activation, gemm_impl)           # psum'd
    out = out.reshape(E_loc, etp, ep_g, C, d)
    my_tp = lax.axis_index(ax) % etp
    mine = jnp.take(out, my_tp, axis=1)                             # (E_loc,ep,C,d)
    mine = mine.transpose(1, 0, 2, 3)
    ret = lax.all_to_all(mine, ax, 0, 0, axis_index_groups=ctx.tp_groups(),
                         tiled=True)
    return ret, None


# ---------------------------------------------------------------------------
# comet: decomposed ring with fused per-chunk MLP + early column-block return
# ---------------------------------------------------------------------------


def _perm(ctx: AxisCtx, group_shift: int, tp_shift: int):
    """Permutation over the model axis: (g, t) -> ((g+group_shift)%ep, (t+tp_shift)%etp)."""
    W, etp, ep = ctx.world, ctx.etp, ctx.ep
    pairs = []
    for r in range(W):
        g, t = r // etp, r % etp
        dst = ((g + group_shift) % ep) * etp + (t + tp_shift) % etp
        pairs.append((r, dst))
    return pairs


def comet_ring_segments(ep: int, ring_group: int, n_col_blocks: int) -> dict:
    """Segment counts of one forward ring as `_comet_ring_fwd` actually
    executes it (etp=1 view): ep//ring_group GroupGEMM macro-steps, each
    consuming ring_group source chunks; chunk slot 0 is local so ep-1
    dispatch ppermutes cross the link; every non-local chunk returns
    n_col_blocks combine ppermutes. core/schedule.py lowers whole-graph
    schedules from these same counts (see comet_ring_counts) and
    tests/test_schedule.py asserts the two never drift apart."""
    g = legalize_ring_group(ep, ring_group)
    return {
        "n_steps": max(1, ep // g),
        "dispatch_hops": max(0, ep - 1),
        "expert_gemms": max(1, ep // g),
        "combine_hops": max(1, n_col_blocks) * max(0, ep - 1),
    }


def _census_note(census, op: str, x, pairs):
    """Record one executed ppermute (payload bytes + permutation pairs) in
    a caller-supplied census list — the interpret-mode traffic measurement
    benchmarks/run.py prices per link class. An explicit argument, never a
    module global; None (the default everywhere) records nothing."""
    if census is not None:
        census.append({"op": op, "bytes": int(x.size) * x.dtype.itemsize,
                       "pairs": [list(p) for p in pairs]})


def _comet_ring_fwd(ctx: AxisCtx, send, w, activation: str, n_col: int,
                    blk: int, g: int, gemm_impl: Optional[str],
                    census=None):
    """The forward ring. Returns (blocks, rows_steps, preacts_steps):
    ``blocks`` is the n_col-tuple of (ep, E_loc, C, blk) streamed column
    blocks; ``rows_steps`` stacks each macro-step's dispatched rows and
    ``preacts_steps`` its layer-0 pre-activations — the backward's saved
    residuals. The fused backend saves rows only (its dgrad/wgrad kernels
    recompute the hidden in VMEM, so ``preacts_steps`` is None); unfused
    backends save (gate, up) exactly as XLA autodiff would, spending no
    backward GEMM recompute."""
    ep, E_loc, C, d = send.shape
    ax = ctx.model_axis
    etp = ctx.etp
    n_steps = ep // g
    r = lax.axis_index(ax)
    g_r = r // etp
    fused = _impl(gemm_impl) == "pallas_fused"

    # col_blocks[b][s]: (E_loc, C, blk) — filled in ascending chunk-slot order
    col_blocks: List[List[jnp.ndarray]] = [[] for _ in range(n_col)]
    rows_steps = []
    gate_steps, up_steps = [], []
    for step in range(n_steps):
        # ---- dispatch: receive g source groups' chunks ---------------------
        chunk_rows = []
        for j in range(g):
            s = step * g + j
            to_send = _dyn_chunk(send, (g_r - s) % ep)              # (E_loc,C,d)
            recvs = []
            for o in range(etp):
                if s == 0 and o == 0:
                    recvs.append(to_send)                           # local chunk first
                else:
                    pairs = _perm(ctx, -s, o)
                    _census_note(census, "disp", to_send, pairs)
                    recvs.append(lax.ppermute(to_send, ax, pairs))
            if etp == 1:
                chunk_rows.append(recvs[0])                         # (E_loc,C,d)
            else:
                stacked = jnp.stack(recvs)                          # (etp,E_loc,C,d)
                # reorder by true source tp: chunk from source tp u sits at
                # position o = (t_r - u) % etp
                t_r = r % etp
                order = (t_r - jnp.arange(etp)) % etp
                by_u = jnp.take(stacked, order, axis=0)
                chunk_rows.append(
                    by_u.transpose(1, 0, 2, 3).reshape(E_loc, etp * C, d))
        rows = (chunk_rows[0] if g == 1 else
                jnp.concatenate(chunk_rows, axis=1))   # (E_loc, g*etp*C, d)
        rows_steps.append(rows)

        # ---- macro-step expert MLP, N-decomposed (layer0 + layer1) ---------
        # fused backend: one VMEM-resident kernel per column block;
        # unfused: GEMM1 once (hidden through HBM), GEMM2 per block — with
        # the pre-activations kept as backward residuals
        Rc = etp * C                                    # rows per source chunk
        if fused:
            obs = mlp_col_blocks(rows, w, activation, n_col, blk, gemm_impl)
        else:
            gate, up = _mlp_preacts(rows, w, activation, gemm_impl)
            h = activate(activation, gate, up)
            obs = [expert_gemm2(h, w, (b * blk, blk), gemm_impl)
                   for b in range(n_col)]
            if gate is not None:
                gate_steps.append(gate)
            up_steps.append(up)
        for b, ob in enumerate(obs):
            ob = _etp_psum(ctx, ob)                     # (E_loc, g*Rc, blk)
            for j in range(g):
                s = step * g + j
                obj = lax.slice_in_dim(ob, j * Rc, (j + 1) * Rc, axis=1)
                if etp > 1:
                    ob_u = obj.reshape(E_loc, etp, C, blk)
                    t_r = r % etp
                    ob_mine = jnp.take(ob_u, t_r, axis=1)           # (E_loc,C,blk)
                else:
                    ob_mine = obj
                if s == 0:
                    col_blocks[b].append(ob_mine)
                else:
                    pairs = _perm(ctx, s, 0)
                    _census_note(census, "comb", ob_mine, pairs)
                    col_blocks[b].append(
                        lax.ppermute(ob_mine, ax, pairs))

    blocks = tuple(jnp.stack(cb) for cb in col_blocks)  # n_col × (ep,E_loc,C,blk)
    preacts_steps = None if fused else (
        jnp.stack(gate_steps) if gate_steps else None, jnp.stack(up_steps))
    return blocks, jnp.stack(rows_steps), preacts_steps


def _comet_ring_bwd(ctx: AxisCtx, rows_steps, preacts_steps, w, cts,
                    activation: str, n_col: int, blk: int, g: int,
                    send_shape, send_dtype, gemm_impl: Optional[str]):
    """The backward ring — the same decomposed schedule run in reverse
    roles. Per macro-step: the dY column blocks for its chunk slots travel
    the reverse return-permutes (slot 0 is local) and, under ETP, are
    re-assembled by a scatter-at-my-tp + subgroup psum (the transpose of
    the forward's psum + take); the per-chunk dgrad/wgrad then consumes
    them block by block while the dX chunks ride the transposed dispatch
    permutes back to their source rank — each of those transfers overlaps
    the next macro-step's GEMMs exactly as in the forward. dW accumulates
    across macro-steps in fp32 and flushes once."""
    ep, E_loc, C, d = send_shape
    ax = ctx.model_axis
    etp = ctx.etp
    n_steps = ep // g
    Rc = etp * C
    r = lax.axis_index(ax)
    g_r = r // etp
    t_r = r % etp

    d_send = jnp.zeros(send_shape, send_dtype)
    dw_acc: Dict[str, jnp.ndarray] = {
        k: jnp.zeros(v.shape, jnp.float32) for k, v in w.items()}
    for step in range(n_steps):
        # ---- dY: reverse return-permutes, per column block ----------------
        dys = []
        for b in range(n_col):
            parts = []
            for j in range(g):
                s = step * g + j
                dy_src = cts[b][s]                      # (E_loc, C, blk)
                if s == 0:
                    dy_j = dy_src
                else:
                    dy_j = lax.ppermute(dy_src, ax, _perm(ctx, -s, 0))
                if etp > 1:
                    full = jnp.zeros((E_loc, etp, C, blk), dy_j.dtype)
                    dy_j = full.at[:, t_r].set(dy_j).reshape(E_loc, Rc, blk)
                parts.append(dy_j if etp > 1 else dy_j.reshape(E_loc, C, blk))
            dy_b = parts[0] if g == 1 else jnp.concatenate(parts, axis=1)
            if etp > 1:
                # transpose of (psum over the subgroup → take my tp slice)
                dy_b = lax.psum(dy_b, ax, axis_index_groups=ctx.etp_groups())
            dys.append(dy_b)                            # (E_loc, g*Rc, blk)

        # ---- per-chunk dgrad + wgrad ---------------------------------------
        rows = rows_steps[step]                         # (E_loc, g*Rc, d)
        preacts = None if preacts_steps is None else (
            None if preacts_steps[0] is None else preacts_steps[0][step],
            preacts_steps[1][step])
        d_rows, dw = _mlp_bwd(rows, w, activation, dys, blk, gemm_impl,
                              preacts)
        for k in dw_acc:
            dw_acc[k] = dw_acc[k] + dw[k].astype(jnp.float32)

        # ---- dX: transposed dispatch permutes back to the source ----------
        for j in range(g):
            s = step * g + j
            dcr = lax.slice_in_dim(d_rows, j * Rc, (j + 1) * Rc, axis=1)
            if etp > 1:
                by_u = dcr.reshape(E_loc, etp, C, d)
            arrivals = None
            for o in range(etp):
                if etp > 1:
                    piece = jnp.take(by_u, (t_r - o) % etp, axis=1)
                else:
                    piece = dcr
                if s == 0 and o == 0:
                    got = piece
                else:
                    got = lax.ppermute(piece, ax, _perm(ctx, s, -o))
                arrivals = got if arrivals is None else arrivals + got
            # the summed arrivals are the gradient of the chunk THIS rank
            # dispatched at slot s (summing also merges the etp partials)
            d_send = lax.dynamic_update_index_in_dim(
                d_send, arrivals.astype(send_dtype), (g_r - s) % ep, axis=0)
    return d_send, _cast_like(dw_acc, w)


def transport_comet_blocks(ctx: AxisCtx, send, w, activation: str,
                           n_col_blocks: int = 1, ring_group: int = 1,
                           gemm_impl: Optional[str] = None,
                           custom_vjp: bool = True, census=None):
    """The comet ring, exposing the layer-1 N-decomposition to the caller:
    returns (blocks, rot) where ``blocks`` is a list of ``n_col`` arrays
    (ep, E_loc, C, blk) — column block b of every chunk's expert output —
    and chunk slot s holds outputs for destination group (rot - s) % ep.

    This is the streaming-consumer interface: block b's array depends only
    on block-b compute and return permutes, so a per-block combine (the
    paper's layer-1 consumer) can start as soon as its block arrives and
    overlap the remaining blocks' GEMM + return traffic, instead of waiting
    for the full-width concatenation.

    ring_group g: number of source-rank chunks fused into ONE GroupGEMM
    macro-step (ep/g steps total). g=1 is the finest overlap (paper default);
    larger g trades overlap granularity for arithmetic intensity — each
    macro-step reads the expert weights once for g chunks, so weight HBM
    traffic and backward dW-accumulator traffic scale ×(g/ep) relative to
    ×1. The adaptive layer picks g from the roofline balance (§3.2.2: the
    same compute-vs-comm division the paper tunes with thread-block counts).

    Knob legalization is the adaptive layer's shared helpers — identical to
    what the tuner ranked and persisted, so plan and execution agree.

    ``custom_vjp=True`` (default) installs the decomposed backward ring
    (module docstring); False leaves XLA autodiff's transposed program —
    the baseline the gradient-equivalence tests difference against."""
    ep, E_loc, C, d = send.shape

    n_col = legalize_n_col(d, n_col_blocks)
    blk = d // n_col

    if not ctx.active or ctx.world == 1:
        if not custom_vjp:
            out, _ = transport_naive(ctx, send, w, activation, gemm_impl)
            return [lax.slice_in_dim(out, b * blk, (b + 1) * blk, axis=-1)
                    for b in range(n_col)], None

        # Degenerate (single-rank) ring: the forward is exactly the naive
        # path; the backward still runs the decomposed per-column-block
        # consumption so the dgrad/wgrad machinery is exercised (and tested)
        # without a mesh.
        @jax.custom_vjp
        def local(send_, w_):
            out, _ = transport_naive(ctx, send_, w_, activation, gemm_impl)
            return tuple(
                lax.slice_in_dim(out, b * blk, (b + 1) * blk, axis=-1)
                for b in range(n_col))

        def local_fwd(send_, w_):
            return local(send_, w_), (send_, w_)

        def local_bwd(res, cts):
            send_, w_ = res
            ep_, E_loc_, C_, d_ = send_.shape
            rows = send_.transpose(1, 0, 2, 3).reshape(E_loc_, ep_ * C_, d_)
            dys = [ct.transpose(1, 0, 2, 3).reshape(E_loc_, ep_ * C_, blk)
                   for ct in cts]
            d_rows, dw = _mlp_bwd(rows, w_, activation, dys, blk, gemm_impl)
            d_send = d_rows.reshape(E_loc_, ep_, C_, d_).transpose(1, 0, 2, 3)
            return d_send.astype(send_.dtype), _cast_like(dw, w_)

        local.defvjp(local_fwd, local_bwd)
        return list(local(send, w)), None

    g = legalize_ring_group(ep, ring_group)
    ax = ctx.model_axis
    rot = lax.axis_index(ax) // ctx.etp

    if not custom_vjp:
        blocks, _, _ = _comet_ring_fwd(ctx, send, w, activation, n_col, blk,
                                       g, gemm_impl, census=census)
        return list(blocks), rot

    send_shape, send_dtype = send.shape, send.dtype

    @jax.custom_vjp
    def ring(send_, w_):
        blocks, _, _ = _comet_ring_fwd(ctx, send_, w_, activation, n_col,
                                       blk, g, gemm_impl)
        return blocks

    def ring_fwd(send_, w_):
        blocks, rows_steps, preacts_steps = _comet_ring_fwd(
            ctx, send_, w_, activation, n_col, blk, g, gemm_impl)
        return blocks, (rows_steps, preacts_steps, w_)

    def ring_bwd(res, cts):
        rows_steps, preacts_steps, w_ = res
        return _comet_ring_bwd(ctx, rows_steps, preacts_steps, w_, cts,
                               activation, n_col, blk, g, send_shape,
                               send_dtype, gemm_impl)

    ring.defvjp(ring_fwd, ring_bwd)
    return list(ring(send, w)), rot


def transport_comet(ctx: AxisCtx, send, w, activation: str,
                    n_col_blocks: int = 1, ring_group: int = 1,
                    gemm_impl: Optional[str] = None,
                    custom_vjp: bool = True):
    """Full-width comet transport: returns (recv_out (ep, E_loc, C, d), rot).
    Concatenates the streamed column blocks — callers wanting the per-block
    overlap (plan knob ``fused_combine``) use ``transport_comet_blocks``."""
    blocks, rot = transport_comet_blocks(ctx, send, w, activation,
                                         n_col_blocks=n_col_blocks,
                                         ring_group=ring_group,
                                         gemm_impl=gemm_impl,
                                         custom_vjp=custom_vjp)
    out = blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks, axis=-1)
    return out, rot


def _dyn_chunk(send, g):
    """send: (ep, E_loc, C, d); g traced -> (E_loc, C, d)."""
    return lax.dynamic_index_in_dim(send, g, axis=0, keepdims=False)


# ---------------------------------------------------------------------------
# comet_hier: the two-level (intra-node × inter-node) decomposed ring, with
# an optional low-precision wire format for dispatch payloads and combine
# partials.
#
# The EP axis is factored as ep = n_nodes × intra_group (rank r -> node
# r // intra_group, local slot r % intra_group). Every hop either stays
# inside a node (both endpoints share the node index — the fast NVLink/ICI
# class) or crosses nodes (the slow RDMA/DCN class); a flat comet shift
# s >= 1 always has SOME cross-node pair when intra_group < ep, so a flat
# ppermute completes at the slow class on every remote step. The two-level
# ring instead decomposes each shift into (node_shift, local_shift): of the
# ep-1 remote sub-steps, intra_group-1 are pure intra-node. Sub-steps run
# inter-node FIRST (core/adaptive.hier_step_order) so the slow hops overlap
# the most remaining compute and the cheap intra hops land in the tail.
# Per-chunk GEMM overlap, ring_group macro-step fusion, the streamed
# per-column-block combine and the custom-VJP backward ring all mirror the
# flat comet schedule — only the permutations (and the wire bytes) change.
#
# Wire format (``wire_dtype``): dispatch chunks are quantized ONCE from the
# pre-ring buffer (so the bytes of a chunk are identical no matter which
# sub-step carries it — the rotation-determinism the tests assert) and
# dequantized in fp32 on receive; each combine partial is quantized once
# before its single return hop. Gradients are NEVER wire-quantized: the
# backward ring moves native-width dY/dX and is the gradient of the
# UNQUANTIZED math (straight-through, the standard estimator).
# ---------------------------------------------------------------------------

_FP8_WIRE_MAX = 448.0                  # |max finite| of float8_e4m3fn
_FP8_WIRE_OK = hasattr(jnp, "float8_e4m3fn")


def wire_dtype_supported(wire_dtype: str) -> bool:
    return wire_dtype in WIRE_DTYPES and (
        wire_dtype != "fp8_e4m3" or _FP8_WIRE_OK)


def _wire_encode(x, wire_dtype: str, per_chunk: bool = False):
    """Quantize a payload for the wire. Returns (payload, scale) — scale is
    None for the scale-free formats. ``per_chunk=True`` keeps one symmetric
    scale per leading-axis chunk (the dispatch buffer's ep chunks);
    otherwise one scale covers the tensor (a single combine partial). The
    fp8 path is optim/compression.py's symmetric-amax scheme at fp8 range."""
    if wire_dtype == "fp32":           # identity: native payload dtype
        return x, None
    if wire_dtype == "bf16":
        return x.astype(jnp.bfloat16), None
    assert wire_dtype == "fp8_e4m3", wire_dtype
    xf = x.astype(jnp.float32)
    axes = tuple(range(1, x.ndim)) if per_chunk else tuple(range(x.ndim))
    amax = jnp.max(jnp.abs(xf), axis=axes, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / _FP8_WIRE_MAX
    q = jnp.clip(xf / scale, -_FP8_WIRE_MAX, _FP8_WIRE_MAX)
    return q.astype(jnp.float8_e4m3fn), scale


def _wire_decode(payload, scale, out_dtype):
    """Dequantize a received payload: the scale multiply runs in fp32 (the
    documented fp32-accumulation point) before the cast to ``out_dtype``."""
    if scale is None:
        return payload.astype(out_dtype)
    return (payload.astype(jnp.float32) * scale).astype(out_dtype)


def _hier_perm(ctx: AxisCtx, ig: int, node_shift: int, loc_shift: int,
               tp_shift: int):
    """Permutation over the model axis with the EP group index factored as
    (node, local): (node, loc, t) -> ((node+node_shift) % n_nodes,
    (loc+loc_shift) % ig, (t+tp_shift) % etp)."""
    W, etp, ep = ctx.world, ctx.etp, ctx.ep
    nn = ep // ig
    pairs = []
    for r in range(W):
        grp, t = r // etp, r % etp
        nd, lc = grp // ig, grp % ig
        dg = ((nd + node_shift) % nn) * ig + (lc + loc_shift) % ig
        pairs.append((r, dg * etp + (t + tp_shift) % etp))
    return pairs


def _hier_dst(g_r, sn: int, sl: int, ig: int, nn: int):
    """Chunk slot this rank dispatches at hier sub-step (sn, sl): the
    destination group reached by shifting -sn nodes / -sl local slots.
    ``g_r`` is the (traced) EP group index."""
    return ((g_r // ig - sn) % nn) * ig + (g_r % ig - sl) % ig


def comet_hier_segments(ep: int, ring_group: int, n_col_blocks: int,
                        intra_group: int) -> dict:
    """Segment counts of one hierarchical forward ring. The loop structure
    (macro-steps, dispatch hops, combine hops) is IDENTICAL to the flat
    ring — the hierarchy re-routes hops, it does not add or remove any —
    plus the per-class split the topology cost model prices."""
    seg = comet_ring_segments(ep, ring_group, n_col_blocks)
    ig = legalize_intra_group(ep, intra_group)
    seg["intra_hops"] = ig - 1
    seg["inter_hops"] = max(0, ep - ig)
    return seg


def _comet_hier_fwd(ctx: AxisCtx, send, w, activation: str, n_col: int,
                    blk: int, g: int, ig: int, wire_dtype: str,
                    gemm_impl: Optional[str], census=None):
    """The hierarchical forward ring. Identical schedule to
    ``_comet_ring_fwd`` — per macro-step: receive g chunks, one GroupGEMM,
    stream n_col column blocks back — but every permute decomposes into the
    two-level (node_shift, local_shift) map and payloads ride the wire
    format. Returns (blocks, rows_steps, preacts_steps) with ``blocks`` in
    HIER SUB-STEP order (the wrapper reorders to destination order)."""
    ep, E_loc, C, d = send.shape
    ax = ctx.model_axis
    etp = ctx.etp
    nn = ep // ig
    n_steps = ep // g
    r = lax.axis_index(ax)
    g_r = r // etp
    t_r = r % etp
    fused = _impl(gemm_impl) == "pallas_fused"
    shifts = hier_step_order(ep, ig)

    # quantize ALL dispatch chunks once, before any permute: the bytes of a
    # chunk are the same no matter which sub-step (or link class) carries
    # it, and the per-chunk scales travel with their payloads
    pay, scales = _wire_encode(send, wire_dtype, per_chunk=True)

    col_blocks: List[List[jnp.ndarray]] = [[] for _ in range(n_col)]
    rows_steps = []
    gate_steps, up_steps = [], []
    for step in range(n_steps):
        # ---- dispatch: receive g source groups' chunks ---------------------
        chunk_rows = []
        for j in range(g):
            s = step * g + j
            sn, sl = shifts[s]
            hd = _hier_dst(g_r, sn, sl, ig, nn)
            to_send = _dyn_chunk(pay, hd)                           # (E_loc,C,d)
            sc = None if scales is None else _dyn_chunk(scales, hd)
            recvs = []
            for o in range(etp):
                if s == 0 and o == 0:
                    recvs.append(_wire_decode(to_send, sc, send.dtype))
                else:
                    pairs = _hier_perm(ctx, ig, -sn, -sl, o)
                    _census_note(census, "disp", to_send, pairs)
                    got = lax.ppermute(to_send, ax, pairs)
                    gsc = (None if sc is None
                           else lax.ppermute(sc, ax, pairs))
                    recvs.append(_wire_decode(got, gsc, send.dtype))
            if etp == 1:
                chunk_rows.append(recvs[0])                         # (E_loc,C,d)
            else:
                stacked = jnp.stack(recvs)                          # (etp,E_loc,C,d)
                order = (t_r - jnp.arange(etp)) % etp
                by_u = jnp.take(stacked, order, axis=0)
                chunk_rows.append(
                    by_u.transpose(1, 0, 2, 3).reshape(E_loc, etp * C, d))
        rows = (chunk_rows[0] if g == 1 else
                jnp.concatenate(chunk_rows, axis=1))   # (E_loc, g*etp*C, d)
        rows_steps.append(rows)

        # ---- macro-step expert MLP, N-decomposed ---------------------------
        Rc = etp * C
        if fused:
            obs = mlp_col_blocks(rows, w, activation, n_col, blk, gemm_impl)
        else:
            gate, up = _mlp_preacts(rows, w, activation, gemm_impl)
            h = activate(activation, gate, up)
            obs = [expert_gemm2(h, w, (b * blk, blk), gemm_impl)
                   for b in range(n_col)]
            if gate is not None:
                gate_steps.append(gate)
            up_steps.append(up)
        for b, ob in enumerate(obs):
            ob = _etp_psum(ctx, ob)                     # (E_loc, g*Rc, blk)
            for j in range(g):
                s = step * g + j
                sn, sl = shifts[s]
                obj = lax.slice_in_dim(ob, j * Rc, (j + 1) * Rc, axis=1)
                if etp > 1:
                    ob_u = obj.reshape(E_loc, etp, C, blk)
                    ob_mine = jnp.take(ob_u, t_r, axis=1)           # (E_loc,C,blk)
                else:
                    ob_mine = obj
                if s == 0:
                    col_blocks[b].append(ob_mine)
                else:
                    # one combine partial = one hop: quantize once before
                    # its return permute, dequantize (fp32 multiply) on
                    # arrival — combine accumulation order is untouched
                    pb, psc = _wire_encode(ob_mine, wire_dtype)
                    pairs = _hier_perm(ctx, ig, sn, sl, 0)
                    _census_note(census, "comb", pb, pairs)
                    got = lax.ppermute(pb, ax, pairs)
                    gsc = (None if psc is None
                           else lax.ppermute(psc, ax, pairs))
                    col_blocks[b].append(
                        _wire_decode(got, gsc, ob_mine.dtype))

    blocks = tuple(jnp.stack(cb) for cb in col_blocks)  # n_col × (ep,E_loc,C,blk)
    preacts_steps = None if fused else (
        jnp.stack(gate_steps) if gate_steps else None, jnp.stack(up_steps))
    return blocks, jnp.stack(rows_steps), preacts_steps


def _comet_hier_bwd(ctx: AxisCtx, rows_steps, preacts_steps, w, cts,
                    activation: str, n_col: int, blk: int, g: int, ig: int,
                    send_shape, send_dtype, gemm_impl: Optional[str]):
    """The hierarchical backward ring — ``_comet_ring_bwd`` on the
    two-level permutes. ``cts`` arrive in HIER SUB-STEP order (the
    destination-order reorder lives OUTSIDE the custom_vjp, so autodiff
    transposes it before this runs). dY rides the inverse return permutes,
    dX the inverse dispatch permutes, both at NATIVE width — gradients are
    never wire-quantized (straight-through w.r.t. the wire format)."""
    ep, E_loc, C, d = send_shape
    ax = ctx.model_axis
    etp = ctx.etp
    nn = ep // ig
    n_steps = ep // g
    Rc = etp * C
    r = lax.axis_index(ax)
    g_r = r // etp
    t_r = r % etp
    shifts = hier_step_order(ep, ig)

    d_send = jnp.zeros(send_shape, send_dtype)
    dw_acc: Dict[str, jnp.ndarray] = {
        k: jnp.zeros(v.shape, jnp.float32) for k, v in w.items()}
    for step in range(n_steps):
        # ---- dY: inverse return-permutes, per column block ----------------
        dys = []
        for b in range(n_col):
            parts = []
            for j in range(g):
                s = step * g + j
                sn, sl = shifts[s]
                dy_src = cts[b][s]                      # (E_loc, C, blk)
                if s == 0:
                    dy_j = dy_src
                else:
                    dy_j = lax.ppermute(dy_src, ax,
                                        _hier_perm(ctx, ig, -sn, -sl, 0))
                if etp > 1:
                    full = jnp.zeros((E_loc, etp, C, blk), dy_j.dtype)
                    dy_j = full.at[:, t_r].set(dy_j).reshape(E_loc, Rc, blk)
                parts.append(dy_j if etp > 1 else dy_j.reshape(E_loc, C, blk))
            dy_b = parts[0] if g == 1 else jnp.concatenate(parts, axis=1)
            if etp > 1:
                dy_b = lax.psum(dy_b, ax, axis_index_groups=ctx.etp_groups())
            dys.append(dy_b)                            # (E_loc, g*Rc, blk)

        # ---- per-chunk dgrad + wgrad ---------------------------------------
        rows = rows_steps[step]                         # (E_loc, g*Rc, d)
        preacts = None if preacts_steps is None else (
            None if preacts_steps[0] is None else preacts_steps[0][step],
            preacts_steps[1][step])
        d_rows, dw = _mlp_bwd(rows, w, activation, dys, blk, gemm_impl,
                              preacts)
        for k in dw_acc:
            dw_acc[k] = dw_acc[k] + dw[k].astype(jnp.float32)

        # ---- dX: inverse dispatch permutes back to the source -------------
        for j in range(g):
            s = step * g + j
            sn, sl = shifts[s]
            dcr = lax.slice_in_dim(d_rows, j * Rc, (j + 1) * Rc, axis=1)
            if etp > 1:
                by_u = dcr.reshape(E_loc, etp, C, d)
            arrivals = None
            for o in range(etp):
                if etp > 1:
                    piece = jnp.take(by_u, (t_r - o) % etp, axis=1)
                else:
                    piece = dcr
                if s == 0 and o == 0:
                    got = piece
                else:
                    got = lax.ppermute(piece, ax,
                                       _hier_perm(ctx, ig, sn, sl, -o))
                arrivals = got if arrivals is None else arrivals + got
            d_send = lax.dynamic_update_index_in_dim(
                d_send, arrivals.astype(send_dtype),
                _hier_dst(g_r, sn, sl, ig, nn), axis=0)
    return d_send, _cast_like(dw_acc, w)


def _hier_dest_order(g_r, ep: int, ig: int):
    """Traced index array mapping destination order to hier sub-step order:
    ``order[dest]`` = the sub-step whose shift carried this rank's chunk
    for destination group ``dest`` (the inverse of ``_hier_dst`` under the
    ``hier_step_order`` enumeration)."""
    nn = ep // ig
    dd = jnp.arange(ep)
    sn = (g_r // ig - dd // ig) % nn
    sl = (g_r % ig - dd % ig) % ig
    return jnp.where(sn == 0,
                     jnp.where(sl == 0, 0, (nn - 1) * ig + sl),
                     (sn - 1) * ig + sl + 1)


def transport_comet_hier(ctx: AxisCtx, send, w, activation: str,
                         n_col_blocks: int = 1, ring_group: int = 1,
                         intra_group: int = 1, wire_dtype: str = "fp32",
                         gemm_impl: Optional[str] = None,
                         custom_vjp: bool = True, census=None):
    """The fifth transport: comet's decomposed schedule on the two-level
    intra/inter-node ring with an optional low-precision wire format (see
    the section comment above). Returns (blocks, rot) exactly like
    ``transport_comet_blocks``, with ``rot=None``: the streamed column
    blocks are reordered on-rank into DESTINATION order (slot s holds the
    output of this rank's tokens for destination group s), so ``combine``
    consumes them with its naive-order slot map unchanged.

    ``intra_group``/``wire_dtype`` are plan knobs (plan cache v6),
    legalized/validated here with the SAME shared helpers the tuner uses
    (``legalize_intra_group``; ``WIRE_DTYPES``)."""
    ep, E_loc, C, d = send.shape
    if not wire_dtype_supported(wire_dtype):
        raise ValueError(
            f"wire_dtype {wire_dtype!r} not supported here (known: "
            f"{WIRE_DTYPES}; fp8_e4m3 needs a jax with float8_e4m3fn)")

    n_col = legalize_n_col(d, n_col_blocks)
    blk = d // n_col

    if not ctx.active or ctx.world == 1:
        # Single-rank degenerate path: no hop crosses a wire, but the wire
        # QUANTIZATION must still apply (numerics match a real mesh run) —
        # straight-through, mirroring the mesh backward's unquantized ring.
        if wire_dtype != "fp32":
            pay, sc = _wire_encode(send, wire_dtype, per_chunk=True)
            deq = _wire_decode(pay, sc, send.dtype)
            send = send + lax.stop_gradient(deq - send)
        return transport_comet_blocks(ctx, send, w, activation,
                                      n_col_blocks=n_col_blocks,
                                      ring_group=ring_group,
                                      gemm_impl=gemm_impl,
                                      custom_vjp=custom_vjp)

    g = legalize_ring_group(ep, ring_group)
    ig = legalize_intra_group(ep, intra_group)
    ax = ctx.model_axis
    g_r = lax.axis_index(ax) // ctx.etp
    order = _hier_dest_order(g_r, ep, ig)

    if not custom_vjp:
        blocks, _, _ = _comet_hier_fwd(ctx, send, w, activation, n_col, blk,
                                       g, ig, wire_dtype, gemm_impl,
                                       census=census)
        return [jnp.take(bk, order, axis=0) for bk in blocks], None

    send_shape, send_dtype = send.shape, send.dtype

    @jax.custom_vjp
    def ring(send_, w_):
        blocks, _, _ = _comet_hier_fwd(ctx, send_, w_, activation, n_col,
                                       blk, g, ig, wire_dtype, gemm_impl)
        return blocks

    def ring_fwd(send_, w_):
        blocks, rows_steps, preacts_steps = _comet_hier_fwd(
            ctx, send_, w_, activation, n_col, blk, g, ig, wire_dtype,
            gemm_impl)
        return blocks, (rows_steps, preacts_steps, w_)

    def ring_bwd(res, cts):
        rows_steps, preacts_steps, w_ = res
        return _comet_hier_bwd(ctx, rows_steps, preacts_steps, w_, cts,
                               activation, n_col, blk, g, ig, send_shape,
                               send_dtype, gemm_impl)

    ring.defvjp(ring_fwd, ring_bwd)
    # the destination-order reorder stays OUTSIDE the custom_vjp: autodiff
    # transposes the take, so the backward ring sees sub-step-order cts
    return [jnp.take(bk, order, axis=0) for bk in ring(send, w)], None


# ---------------------------------------------------------------------------
# bcast: decode path — tokens replicated over the model axis
# ---------------------------------------------------------------------------


def transport_bcast(ctx: AxisCtx, buf_full, w, activation: str,
                    gemm_impl: Optional[str] = None):
    """buf_full: (E, C, d) — identical on every model rank. Each rank runs its
    own expert slice; a single psum over the model axis both sums ETP partials
    and merges expert groups. Returns (E, C, d) fully combined."""
    E, C, d = buf_full.shape
    if not ctx.active or ctx.world == 1:
        rows = buf_full
        out = expert_mlp(ctx, rows, w, activation, gemm_impl)
        return out
    ax = ctx.model_axis
    E_loc = E // ctx.ep
    r = lax.axis_index(ax)
    g_r = r // ctx.etp
    mine = lax.dynamic_slice_in_dim(buf_full, g_r * E_loc, E_loc, axis=0)
    out = _mlp_out(mine, w, activation, gemm_impl)                  # partial
    full = jnp.zeros((E, C, d), out.dtype)
    full = lax.dynamic_update_slice_in_dim(full, out, g_r * E_loc, axis=0)
    return lax.psum(full, ax)
