"""Paged decode attention as a Pallas-TPU kernel.

One decode token per row attends over that row's cache positions
``[kv_start, pos]``, read straight from the shared page pool through the
row's block table. Nothing per row is materialised in HBM: the kernel walks
only the pages that hold live positions, ``pages_per_block`` at a time, with
the next block's page copies in flight while the current one is reduced.

Layout: a layer's pool ``(P, page, Hkv, hd)`` is read as
``(P, page, Hkv*hd)``, so one page is one DMA holding every KV head with
its lanes dense. (Mosaic cannot slice an HBM array whose minor dim is
under 128 lanes, which ``hd`` 64 is: the pool cannot be read as
``(..., hd)`` rows in place.) The query heads of a row are laid out
block-diagonally, ``q_bd[h, g*hd:(g+1)*hd] = q[h]`` for ``g = h // rep``
and zero elsewhere, so one ``(H, Hkv*hd) x (Hkv*hd, T)`` product gives
every head its scores against its own KV head (the zeros add exact zeros)
without slicing the lanes of a page, and no KV head is repeated. ``P.V``
likewise gives each head ``Hkv`` candidate outputs; the wrapper keeps the
head's own.

Numerics: ``Q.K^T`` in the pool's dtype with float32 accumulation, scaled
by ``1/sqrt(hd)``; online-softmax state (max, sum, accumulator) in float32;
``P.V`` with ``P`` in float32 and the V block upcast in VMEM, at full
float32 contraction precision. A position is valid iff
``kv_start <= i <= pos``. A row whose table maps its position's block to
the null page (a free slot) reads nothing and returns zeros.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import COMPILER_PARAMS

NEG_INF = -1e30
NULL_PAGE = 0          # the pool's reserved page (models/attention.py)
# 16 pages of 16 positions: 256 positions a block. A call at the benchmark
# cell's shapes (20 live rows of 32) took 0.390 / 0.358 / 0.359 ms with
# 8 / 16 / 32 pages a block on a TPU v5e; a row's pages past its position
# are never copied
DEFAULT_PAGES_PER_BLOCK = 16


def _kernel(tbl_ref, lo_ref, hi_ref, pos_ref, start_ref,     # SMEM
            q_ref, k_hbm, v_hbm,                              # inputs
            o_ref,                                            # output
            kbuf, vbuf, sem, m_ref, l_ref, acc_ref,           # scratch
            *, nb: int, page: int, ppb: int, scale: float, precision):
    b = pl.program_id(0)
    lo, hi = lo_ref[b], hi_ref[b]          # pages [lo, hi) of row b
    pos, start = pos_ref[b], start_ref[b]
    T = ppb * page
    n_blocks = (hi - lo + ppb - 1) // ppb

    def page_copies(j, slot):
        """(needed, K copy, V copy) for each page of block j."""
        out = []
        for i in range(ppb):
            blk = lo + j * ppb + i
            pid = tbl_ref[b * nb + jnp.minimum(blk, nb - 1)]
            dst = pl.ds(i * page, page)
            out.append((
                blk < hi,
                pltpu.make_async_copy(k_hbm.at[pid], kbuf.at[slot, dst],
                                      sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[pid], vbuf.at[slot, dst],
                                      sem.at[1, slot])))
        return out

    def start_block(j, slot):
        for needed, ck, cv in page_copies(j, slot):
            @pl.when(needed)
            def _():
                ck.start()
                cv.start()

    def wait_block(j, slot):
        for needed, ck, cv in page_copies(j, slot):
            @pl.when(needed)
            def _():
                ck.wait()
                cv.wait()

    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(n_blocks > 0)
    def _():
        start_block(0, 0)

    def body(j, carry):
        slot = j % 2

        @pl.when(j + 1 < n_blocks)
        def _():
            start_block(j + 1, 1 - slot)

        wait_block(j, slot)
        base = (lo + j * ppb) * page
        s = lax.dot_general(q_ref[...], kbuf[slot].astype(q_ref.dtype),
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32,
                            precision=precision) * scale       # (H, T)
        i_row = base + lax.broadcasted_iota(jnp.int32, (1, T), 1)
        valid = (i_row >= start) & (i_row <= pos)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        # rows of the block past the row's pages were never copied: zero
        # them so stale VMEM cannot reach the sum through 0 * inf
        i_col = base + lax.broadcasted_iota(jnp.int32, (T, 1), 0)
        v = jnp.where((i_col >= start) & (i_col <= pos),
                      vbuf[slot].astype(jnp.float32), 0.0)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32,
            precision=lax.Precision.HIGHEST)
        m_ref[...] = m_new
        return carry

    lax.fori_loop(0, n_blocks, body, 0)
    o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                  ).astype(o_ref.dtype)


def page_ranges(pos, kv_start, block_table, page: int):
    """Per row, the pages ``[lo, hi)`` of its table that hold positions
    ``kv_start..pos``; ``hi == lo`` for a row whose table maps its
    position's block to the null page (a free slot)."""
    nb = block_table.shape[1]
    lo = jnp.clip(kv_start // page, 0, nb)
    last = jnp.clip(pos // page, 0, nb - 1)
    live = jnp.take_along_axis(block_table, last[:, None], axis=1)[:, 0] \
        != NULL_PAGE
    hi = jnp.where(live, jnp.minimum(pos // page + 1, nb), lo)
    return lo, hi


def paged_decode_attention(q, k_pool, v_pool, pos, block_table,
                           kv_start=None, *,
                           pages_per_block: int = DEFAULT_PAGES_PER_BLOCK,
                           interpret: bool = False):
    """q: (B, 1, H, hd); pools: (P, page, Hkv, hd); pos: () or (B,) each
    row's current position; block_table: (B, nb) int32 page ids;
    kv_start: optional ()/(B,) first valid position. Returns (B, 1, H, hd)
    in q's dtype."""
    B, _, H, hd = q.shape
    P, page, Hkv, _ = k_pool.shape
    nb = block_table.shape[1]
    rep = H // Hkv
    D = Hkv * hd
    ppb = max(1, min(pages_per_block, nb))
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    start = (jnp.zeros((B,), jnp.int32) if kv_start is None else
             jnp.broadcast_to(jnp.asarray(kv_start, jnp.int32).reshape(-1),
                              (B,)))
    block_table = block_table.astype(jnp.int32)
    lo, hi = page_ranges(pos, start, block_table, page)
    # block-diagonal queries: head h holds its vector in its KV head's lanes
    own = (jnp.arange(H) // rep)[:, None] == jnp.arange(Hkv)[None, :]
    q_bd = jnp.where(own[None, :, :, None], q[:, 0, :, None, :],
                     jnp.zeros((), q.dtype)).reshape(B, H, D)
    # a float32 query against a bf16 pool contracts in float32, as today
    q_bd = q_bd.astype(jnp.promote_types(q.dtype, k_pool.dtype))
    precision = (lax.Precision.HIGHEST if q_bd.dtype == jnp.float32
                 else lax.Precision.DEFAULT)
    q_spec = pl.BlockSpec((None, H, D), lambda b, *_: (b, 0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, nb=nb, page=page, ppb=ppb,
                          scale=1.0 / float(hd) ** 0.5, precision=precision),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(B,),
            in_specs=[q_spec, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((2, ppb * page, D), k_pool.dtype),
                pltpu.VMEM((2, ppb * page, D), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, D), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(block_table.reshape(-1), lo, hi, pos, start, q_bd,
      k_pool.reshape(P, page, D), v_pool.reshape(P, page, D))
    # each head keeps the output of its own KV head's lanes
    out = jnp.take_along_axis(out.reshape(B, H, Hkv, hd),
                              (jnp.arange(H) // rep)[None, :, None, None],
                              axis=2)
    return out.reshape(B, 1, H, hd)
