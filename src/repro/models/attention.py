"""GQA attention: chunked online-softmax (flash-style, pure jnp), decode w/ KV
cache, cross-attention. The chunked path keeps activation memory O(S) so the
32k prefill cells lower without a (S, S) score tensor.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import ParamDecl, apply_rope

NEG_INF = -1e30
NULL_PAGE = 0          # paged KV: page id 0 is reserved, never allocated


# ---------------------------------------------------------------------------
# Schema
# ---------------------------------------------------------------------------


def attn_schema(cfg, a, cross: bool = False) -> Dict[str, ParamDecl]:
    d = cfg.d_model
    s = {
        "wq": ParamDecl((d, a.n_heads * a.head_dim), ("embed", "qheads")),
        "wk": ParamDecl((d, a.n_kv_heads * a.head_dim), ("embed", "kvheads")),
        "wv": ParamDecl((d, a.n_kv_heads * a.head_dim), ("embed", "kvheads")),
        "wo": ParamDecl((a.n_heads * a.head_dim, d), ("qheads", "embed")),
    }
    if a.qkv_bias:
        s["bq"] = ParamDecl((a.n_heads * a.head_dim,), ("qheads",), "zeros")
        s["bk"] = ParamDecl((a.n_kv_heads * a.head_dim,), ("kvheads",), "zeros")
        s["bv"] = ParamDecl((a.n_kv_heads * a.head_dim,), ("kvheads",), "zeros")
    return s


def qkv(p, a, x, positions=None, rope: bool = True):
    B, S, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"].astype(q.dtype), k + p["bk"].astype(k.dtype), v + p["bv"].astype(v.dtype)
    q = q.reshape(B, S, a.n_heads, a.head_dim)
    k = k.reshape(B, S, a.n_kv_heads, a.head_dim)
    v = v.reshape(B, S, a.n_kv_heads, a.head_dim)
    if rope and positions is not None:
        q = apply_rope(q, positions, a.rope_theta)
        k = apply_rope(k, positions, a.rope_theta)
    return q, k, v


# ---------------------------------------------------------------------------
# Core attention math
# ---------------------------------------------------------------------------


def _expand_kv(k, n_heads):
    """(B, S, Hkv, hd) -> (B, S, Hq, hd) by repeat."""
    B, S, Hkv, hd = k.shape
    rep = n_heads // Hkv
    if rep == 1:
        return k
    return jnp.repeat(k, rep, axis=2)


def dense_attention(q, k, v, causal: bool, q_offset: int = 0,
                    kv_mask=None, q_pos=None, kv_pos=None) -> jnp.ndarray:
    """Reference O(S^2) path for short sequences. q: (B,Sq,H,hd), k/v: (B,Sk,Hkv,hd).

    q_pos/kv_pos: optional (B, Sq)/(B, Sk) absolute positions for the causal
    mask — required when q is sequence-sharded (local row i is NOT global
    position i)."""
    B, Sq, H, hd = q.shape
    k = _expand_kv(k, H)
    v = _expand_kv(v, H)
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    Sk = k.shape[1]
    if causal:
        if q_pos is not None:
            kp = kv_pos if kv_pos is not None else \
                jnp.broadcast_to(jnp.arange(Sk)[None, :], (B, Sk))
            mask = kp[:, None, None, :] <= q_pos[:, None, :, None]
            scores = jnp.where(mask, scores, NEG_INF)
        else:
            qi = jnp.arange(Sq) + q_offset
            ki = jnp.arange(Sk)
            scores = jnp.where(ki[None, :] <= qi[:, None], scores, NEG_INF)
    if kv_mask is not None:
        scores = jnp.where(kv_mask[:, None, None, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(jnp.float32))
    return out.astype(q.dtype)


def chunked_attention(q, k, v, causal: bool, q_block: int, kv_block: int,
                      q_offset: int = 0, q_pos=None, kv_pos=None,
                      kv_mask=None) -> jnp.ndarray:
    """Flash-style two-level scan: outer over q blocks, inner over kv blocks
    with running (max, sum, acc). Memory O(q_block * kv_block).
    kv_mask: optional (B, Sk) validity — masked kv columns are excluded
    (pad-token exclusion for mixed-length batched prefill)."""
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    q_block = min(q_block, Sq)
    kv_block = min(kv_block, Sk)
    if Sq % q_block or Sk % kv_block:
        return dense_attention(q, k, v, causal, q_offset,
                               kv_mask=kv_mask, q_pos=q_pos, kv_pos=kv_pos)
    k = _expand_kv(k, H)
    v = _expand_kv(v, H)
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    nq, nk = Sq // q_block, Sk // kv_block
    if q_pos is None:
        q_pos = jnp.broadcast_to(jnp.arange(Sq)[None, :] + q_offset, (B, Sq))
    if kv_pos is None:
        kv_pos = jnp.broadcast_to(jnp.arange(Sk)[None, :], (B, Sk))

    qb = q.reshape(B, nq, q_block, H, hd).transpose(1, 0, 3, 2, 4)   # (nq,B,H,qb,hd)
    kb = k.reshape(B, nk, kv_block, H, hd).transpose(1, 0, 3, 2, 4)
    vb = v.reshape(B, nk, kv_block, H, hd).transpose(1, 0, 3, 2, 4)
    qpb = q_pos.reshape(B, nq, q_block).swapaxes(0, 1)               # (nq,B,qb)
    kpb = kv_pos.reshape(B, nk, kv_block).swapaxes(0, 1)             # (nk,B,kb)
    # the pad-mask select is only scanned in when a mask is actually passed
    # — the maskless training/prefill hot path keeps its pre-serving shape
    kmb = (None if kv_mask is None else
           jnp.broadcast_to(kv_mask, (B, Sk))
           .reshape(B, nk, kv_block).swapaxes(0, 1))                 # (nk,B,kb)

    def q_step(_, qi_and_block):
        qpos, qblk = qi_and_block
        qblk = qblk.astype(jnp.float32) * scale
        m0 = jnp.full((B, H, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, q_block), jnp.float32)
        a0 = jnp.zeros((B, H, q_block, hd), jnp.float32)

        def kv_step(carry, ki_and_block):
            m, l, acc = carry
            if kmb is None:
                kpos, kblk, vblk = ki_and_block
            else:
                kpos, kmask, kblk, vblk = ki_and_block
            s = jnp.einsum("bhqd,bhkd->bhqk", qblk, kblk.astype(jnp.float32))
            if causal:
                mask = kpos[:, None, None, :] <= qpos[:, None, :, None]
                s = jnp.where(mask, s, NEG_INF)
            if kmb is not None:
                s = jnp.where(kmask[:, None, None, :], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p, vblk.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        xs = (kpb, kb, vb) if kmb is None else (kpb, kmb, kb, vb)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), xs)
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return None, out

    _, outs = jax.lax.scan(q_step, None, (qpb, qb))  # (nq,B,H,qb,hd)
    out = outs.transpose(1, 0, 3, 2, 4).reshape(B, Sq, H, hd)
    return out.astype(q.dtype)


def attention(q, k, v, causal: bool, q_block: int = 512, kv_block: int = 1024,
              q_offset: int = 0, dense_threshold: int = 1024,
              q_pos=None, kv_pos=None, kv_mask=None) -> jnp.ndarray:
    Sq, Sk = q.shape[1], k.shape[1]
    if Sq * Sk <= dense_threshold * dense_threshold:
        return dense_attention(q, k, v, causal, q_offset, kv_mask=kv_mask,
                               q_pos=q_pos, kv_pos=kv_pos)
    return chunked_attention(q, k, v, causal, q_block, kv_block, q_offset,
                             q_pos=q_pos, kv_pos=kv_pos, kv_mask=kv_mask)


# ---------------------------------------------------------------------------
# Decode with KV cache
# ---------------------------------------------------------------------------


def _pos_col(pos):
    """Normalize a ()/(B,) position to broadcast against (B, ·, ·, S)."""
    pos = jnp.asarray(pos)
    return pos.reshape((-1, 1, 1, 1)) if pos.ndim else pos


# -- paged (block-table) cache layout ---------------------------------------
# The pool holds fixed-size pages shared by every slot: (n_pages, page, Hkv,
# hd). A block table (B, max_blocks) int32 maps each row's logical block i
# (positions [i*page, (i+1)*page)) to a physical page; entry 0 is the NULL
# page — never allocated, so unmapped blocks gather it (masked by position
# validity) and dead-row writes are steered into it.


def paged_gather(pool, block_table):
    """Materialize the logical per-row cache view from the shared pool.
    pool: (P, page, Hkv, hd); block_table: (B, nb) int32 page ids.
    Returns (B, nb*page, Hkv, hd) — row b's logical positions in order."""
    g = jnp.take(pool, block_table, axis=0)       # (B, nb, page, Hkv, hd)
    B, nb, page, Hkv, hd = g.shape
    return g.reshape(B, nb * page, Hkv, hd)


@jax.named_scope("attn.cache_write")
def paged_update_cache(k_pool, v_pool, k_new, v_new, pos, block_table):
    """Decode write through block tables: insert (B, 1, Hkv, hd) at per-row
    logical position ``pos`` (() or (B,)). Rows whose mapped page is the
    null page (free slots — all-zero table rows) write harmlessly into it.
    Returns the updated pools."""
    P, page, Hkv, hd = k_pool.shape
    B = k_new.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    blk = jnp.clip(pos // page, 0, block_table.shape[1] - 1)
    pid = jnp.take_along_axis(block_table, blk[:, None], axis=1)[:, 0]
    off = pos % page
    # written through the lane-dense (P, page, Hkv*hd) view that the TPU
    # decode kernel reads, so XLA lays each layer's pool out once, to that
    # view: on the TPU the (.., Hkv, hd) view pads an hd of 64 to 128
    # lanes, and the kernel's view would take a second relayout from it
    kf = k_pool.reshape(P, page, Hkv * hd)
    vf = v_pool.reshape(P, page, Hkv * hd)
    kf = kf.at[pid, off].set(k_new[:, 0].reshape(B, -1).astype(kf.dtype))
    vf = vf.at[pid, off].set(v_new[:, 0].reshape(B, -1).astype(vf.dtype))
    return kf.reshape(P, page, Hkv, hd), vf.reshape(P, page, Hkv, hd)


@jax.named_scope("attn.cache_write")
def paged_chunk_update(k_pool, v_pool, k, v, pos_off, block_table, tok_mask):
    """Prefill-chunk write through block tables: k/v (A, C, Hkv, hd) land at
    logical positions pos_off[a] + [0, C). tok_mask (A, C) marks valid
    tokens — tail pads and inactive admission rows are steered to the null
    page, so one stacked call admits several requests without branching.
    Returns the updated pools."""
    P, page, Hkv, hd = k_pool.shape
    A, C = k.shape[:2]
    pos_off = jnp.broadcast_to(jnp.asarray(pos_off, jnp.int32).reshape(-1),
                               (A,))
    positions = pos_off[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    nb = block_table.shape[1]
    blk = positions // page
    pid = jnp.take_along_axis(block_table, jnp.clip(blk, 0, nb - 1), axis=1)
    pid = jnp.where(tok_mask & (blk < nb), pid, NULL_PAGE)
    phys = (pid * page + positions % page).reshape(A * C)
    kf = k_pool.reshape(P * page, Hkv, hd)
    vf = v_pool.reshape(P * page, Hkv, hd)
    kf = kf.at[phys].set(k.reshape(A * C, Hkv, hd).astype(kf.dtype))
    vf = vf.at[phys].set(v.reshape(A * C, Hkv, hd).astype(vf.dtype))
    return kf.reshape(P, page, Hkv, hd), vf.reshape(P, page, Hkv, hd)


def decode_attention(q, k_cache, v_cache, pos, kv_start=None,
                     block_table=None) -> jnp.ndarray:
    """q: (B, 1, H, hd); caches: (B, S, Hkv, hd); pos: () or (B,) per-row
    current index (continuous batching decodes every slot at its OWN
    position). Attends over cache[kv_start : pos+1] via masking (fixed-size
    cache = production decode; the memory-roofline term reads the full
    cache, as real HW does). kv_start: optional ()/(B,) first valid cache
    index — left-padded rows exclude their pad region exactly.
    block_table: optional (B, nb) int32 — the caches are then shared
    (n_pages, page, Hkv, hd) pools and each row's logical view is gathered
    through its table (unmapped blocks hit the null page, masked by the
    position-validity test exactly like stale contiguous rows).
    On the TPU a paged call runs ``kernels/paged_decode_attention.py``
    instead, which reads only each row's live pages, in place; a row whose
    table maps its position to the null page (a free slot) returns zeros."""
    if block_table is not None and jax.default_backend() == "tpu":
        from repro.kernels import ops
        return ops.paged_decode_attention(q, k_cache, v_cache, pos,
                                          block_table, kv_start)
    if block_table is not None:
        k_cache = paged_gather(k_cache, block_table)
        v_cache = paged_gather(v_cache, block_table)
    B, S, Hkv, hd = k_cache.shape
    H = q.shape[2]
    k = _expand_kv(k_cache, H)
    v = _expand_kv(v_cache, H)
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    ar = jnp.arange(S)[None, None, None, :]
    valid = ar <= _pos_col(pos)
    if kv_start is not None:
        valid &= ar >= _pos_col(kv_start)
    s = jnp.where(valid, s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(jnp.float32))
    return out.astype(q.dtype)


def decode_attention_partial(q, k_shard, v_shard, pos, kv_offset,
                             kv_start=None):
    """Flash-decode partial over a LOCAL kv shard. q: (B,1,H,hd); shards:
    (B,S_loc,Hkv,hd); pos: () or (B,); kv_offset: absolute position of shard
    row 0. Returns (m, l, acc): running max (B,H,1), sum (B,H,1), acc
    (B,H,1,hd) — merged across shards by the caller (pmax/psum), the
    split-KV scheme."""
    B, S_loc, Hkv, hd = k_shard.shape
    H = q.shape[2]
    k = _expand_kv(k_shard, H)
    v = _expand_kv(v_shard, H)
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    ar = (kv_offset + jnp.arange(S_loc))[None, None, None, :]
    valid = ar <= _pos_col(pos)
    if kv_start is not None:
        valid &= ar >= _pos_col(kv_start)
    s = jnp.where(valid, s, NEG_INF)
    m = jnp.max(s, axis=-1)                                # (B,H,1)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(valid, p, 0.0)                           # fully-masked shard
    l = jnp.sum(p, axis=-1)                                # (B,H,1)
    acc = jnp.einsum("bhqk,bkhd->bhqd", p, v.astype(jnp.float32))
    return m, l, acc


def merge_decode_partials(m, l, acc, axis_name):
    """Combine split-KV partials across the mesh axis: three tiny
    collectives of (B,H,1[,hd]) instead of all-gathering the cache."""
    m_g = jax.lax.pmax(m, axis_name)
    corr = jnp.exp(m - m_g)
    l_g = jax.lax.psum(l * corr, axis_name)
    acc_g = jax.lax.psum(acc * corr[..., None], axis_name)
    return acc_g / jnp.maximum(l_g[..., None], 1e-30)


@jax.named_scope("attn.cache_write")
def update_cache(k_cache, v_cache, k_new, v_new, pos):
    """Insert (B, S, Hkv, hd) at position pos — () shared across the batch,
    or (B,) per-row write indices (slot-based decode: every slot is at its
    own sequence position; chunked prefill: each row's chunk at its
    offset)."""
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            k_cache, k_new.astype(k_cache.dtype), pos, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            v_cache, v_new.astype(v_cache.dtype), pos, axis=1)
        return k_cache, v_cache

    def row(c, n, p):
        return jax.lax.dynamic_update_slice_in_dim(c, n, p, axis=0)

    k_cache = jax.vmap(row)(k_cache, k_new.astype(k_cache.dtype), pos)
    v_cache = jax.vmap(row)(v_cache, v_new.astype(v_cache.dtype), pos)
    return k_cache, v_cache
