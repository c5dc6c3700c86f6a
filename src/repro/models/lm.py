"""Full model assembly: schema, init, train forward, prefill, decode.

Layers are stacked by *period* (lcm of the hybrid pattern length and the MoE
interleave) and scanned — one period of HLO regardless of depth, which keeps
the 94-layer dry-runs compilable.
"""
from __future__ import annotations

import math
import os
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models import attention as A
from repro.models import blocks as B
from repro.models import ssm as S
from repro.models.common import (ParamDecl, abstract_from_schema, apply_norm,
                                 chunked_xent, ffn_schema, init_from_schema,
                                 norm_schema, sinusoid_positions,
                                 specs_from_schema)
from repro.parallel.mesh import AxisCtx

Pytree = Any


def period_of(cfg) -> int:
    p = max(1, len(cfg.layer_pattern))
    if cfg.moe is not None:
        p = math.lcm(p, cfg.moe.every_k_layers)
    return p


def _stack(schema: Pytree, n: int) -> Pytree:
    def mk(d: ParamDecl):
        return ParamDecl((n,) + d.shape, ("layers",) + d.logical, d.init, d.scale)
    return jax.tree_util.tree_map(mk, schema,
                                  is_leaf=lambda x: isinstance(x, ParamDecl))


def _enc_layer_schema(cfg) -> Dict:
    return {
        "ln1": norm_schema(cfg, cfg.d_model),
        "attn": A.attn_schema(cfg, cfg.attn),
        "ln2": norm_schema(cfg, cfg.d_model),
        "ffn": ffn_schema(cfg, cfg.d_model, cfg.d_ff),
    }


def model_schema(cfg, ctx: AxisCtx) -> Dict:
    d, V = cfg.d_model, cfg.vocab_size
    s: Dict[str, Any] = {"embed": ParamDecl((V, d), ("vocab", "embed"))}
    if not cfg.tie_embeddings:
        s["lm_head"] = ParamDecl((d, V), ("embed", "vocab"))
    s["ln_f"] = norm_schema(cfg, d)
    p = period_of(cfg)
    assert cfg.n_layers % p == 0, (cfg.name, cfg.n_layers, p)
    n_periods = cfg.n_layers // p
    cross = cfg.n_enc_layers > 0
    s["layers"] = [
        _stack(B.layer_schema(cfg, pos, ctx, cross=cross), n_periods)
        for pos in range(p)
    ]
    if cfg.n_enc_layers:
        s["encoder"] = _stack(_enc_layer_schema(cfg), cfg.n_enc_layers)
        s["ln_enc"] = norm_schema(cfg, d)
    return s


def init_params(cfg, key, ctx: AxisCtx = AxisCtx()) -> Pytree:
    return init_from_schema(model_schema(cfg, ctx), key, cfg.param_dtype)


def abstract_params(cfg, ctx: AxisCtx = AxisCtx()) -> Pytree:
    return abstract_from_schema(model_schema(cfg, ctx), cfg.param_dtype)


# ---------------------------------------------------------------------------
# Embedding / io
# ---------------------------------------------------------------------------


@jax.named_scope("lm.embed")
def embed_tokens(cfg, params, tokens):
    """Token ids (..., S) -> embeddings (..., S, d) in the compute dtype."""
    return jnp.take(params["embed"], tokens, axis=0).astype(cfg.compute_dtype)


def embed_inputs(cfg, params, batch, ctx: AxisCtx):
    if "embeds" in batch:                       # stub modality frontend
        h = batch["embeds"].astype(cfg.compute_dtype)
    else:
        h = embed_tokens(cfg, params, batch["tokens"])
    if cfg.n_enc_layers:                        # whisper decoder: abs positions
        Spos = h.shape[1]
        h = h + sinusoid_positions(Spos, cfg.d_model).astype(h.dtype)
    return B._csp(h, ctx, ctx.dp_axes, None, None)


def output_head(cfg, params):
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["lm_head"]


# ---------------------------------------------------------------------------
# Encoder (whisper)
# ---------------------------------------------------------------------------


def encode(cfg, params, frames, ctx: AxisCtx):
    h = frames.astype(cfg.compute_dtype)
    h = h + sinusoid_positions(h.shape[1], cfg.d_model).astype(h.dtype)
    positions = jnp.arange(h.shape[1])[None, :]

    def body(x, p):
        hh = apply_norm(cfg, p["ln1"], x)
        hh, _ = B.attn_apply(cfg, p["attn"], hh, ctx, positions, causal=False,
                             use_rope=False)
        x = x + hh
        hh = apply_norm(cfg, p["ln2"], x)
        from repro.models.common import ffn_apply
        x = x + ffn_apply(cfg, p["ffn"], hh)
        return x, None

    if cfg.remat == "full":
        body = jax.checkpoint(body)
    h, _ = jax.lax.scan(lambda c, p: body(c, p), h, params["encoder"])
    return apply_norm(cfg, params["ln_enc"], h)


# ---------------------------------------------------------------------------
# Train / prefill forward
# ---------------------------------------------------------------------------


def _forward_inputs(cfg, params, batch, ctx: AxisCtx):
    """Shared front of every full-sequence forward: embeddings, pad-aware
    positions, optional encoder output."""
    h = embed_inputs(cfg, params, batch, ctx)
    Bsz, Ssz, _ = h.shape
    mask = batch.get("mask")
    if "positions" in batch:
        positions = batch["positions"]
    elif mask is not None:
        # left-pad aware: position = rank among this row's valid tokens
        positions = jnp.maximum(
            jnp.cumsum(mask.astype(jnp.int32), axis=1) - 1, 0)
    else:
        positions = jnp.broadcast_to(jnp.arange(Ssz)[None, :], (Bsz, Ssz))
    enc_out = None
    if cfg.n_enc_layers:
        enc_out = encode(cfg, params, batch["frames"], ctx)
    return h, positions, mask, enc_out


def forward(cfg, params, batch, ctx: AxisCtx = AxisCtx(),
            return_cache: bool = False):
    """Returns (h_final, aux_loss, cache|None). h_final: (B, S, d).

    batch may carry a ``mask`` (B, S) bool — pad-token validity for
    mixed-length batched prefill. With it, pad keys/values are excluded
    from attention, SSM pad steps become identities, and per-row positions
    are derived from the mask (left-padded rows RoPE from 0 at their first
    real token), so the padded forward is EXACT, not approximate.

    With ``cfg.block_schedule`` set ("sequential" | "overlap") the
    non-cache path runs through the block-schedule IR
    (``forward_scheduled``); prefill (return_cache=True) always keeps the
    scan path."""
    if getattr(cfg, "block_schedule", "") and not return_cache:
        return forward_scheduled(cfg, params, batch, ctx)
    h, positions, mask, enc_out = _forward_inputs(cfg, params, batch, ctx)

    p = period_of(cfg)

    def period_body(carry, layer_params):
        x, aux = carry
        caches = []
        for pos in range(p):
            x, a, ce = B.apply_layer(cfg, pos, layer_params[pos], x, ctx,
                                     positions, enc_out=enc_out,
                                     return_cache=return_cache, mask=mask)
            aux = aux + a
            caches.append(ce)
        out = tuple(caches) if return_cache else None
        return (x, aux), out

    body = period_body
    if cfg.remat == "full" and not return_cache:
        body = jax.checkpoint(period_body)

    (h, aux), caches = jax.lax.scan(
        body, (h, jnp.zeros((), jnp.float32)), tuple(params["layers"]))
    h = apply_norm(cfg, params["ln_f"], h)
    return h, aux, caches


def forward_scheduled(cfg, params, batch, ctx: AxisCtx = AxisCtx()):
    """Block-schedule-IR forward: every layer is lowered to its executed
    segments (models/blocks.py ``block_segments``), the whole-graph segment
    list is ordered by core/schedule.py (``cfg.block_schedule``:
    "sequential" = program order, "overlap" = the greedy earliest-start
    scheduler), and the chosen emission order is interpreted against one
    shared env. Any legal order is a pure permutation over identical
    dataflow, so this is numerically IDENTICAL to the sequential baseline
    — the equivalence the tests assert bitwise.

    Layers are UNROLLED (no scan/remat): the scheduler needs segments of
    DIFFERENT blocks visible in one window, which a scanned period body
    cannot expose. Intended for the paper-shape step benchmarks and
    parity tests, not 94-layer dry-runs."""
    from repro.core.schedule import exec_order

    h, positions, mask, enc_out = _forward_inputs(cfg, params, batch, ctx)
    p = period_of(cfg)
    segs = []
    for i in range(cfg.n_layers):
        lp = jax.tree_util.tree_map(lambda a, i=i: a[i // p],
                                    params["layers"][i % p])
        segs += B.block_segments(cfg, i % p, lp, ctx, positions,
                                 enc_out=enc_out, return_cache=False,
                                 mask=mask, block=i, x_in=f"x{i}",
                                 x_out=f"x{i + 1}")
    program = segs
    segs = exec_order(segs, cfg.block_schedule)
    if os.environ.get("REPRO_VERIFY_SCHEDULE", "1") != "0":
        # trace-time race detector: re-derive RAW/WAR/WAW hazards from the
        # segments' declared reads/writes (NOT the deps the scheduler
        # used) and refuse any order that violates one. Pure Python over
        # a few hundred segments — costs nothing against the jit trace.
        from repro.analysis.verify.schedule_check import \
            assert_exec_order_safe
        assert_exec_order_safe(program, segs)
    env = B.run_segments(segs, {"x0": h})
    aux = jnp.zeros((), jnp.float32)
    for i in range(cfg.n_layers):
        a = env.get(f"L{i}.aux")
        if a is not None:
            aux = aux + a
    h = apply_norm(cfg, params["ln_f"], env[f"x{cfg.n_layers}"])
    return h, aux, None


def loss_fn(cfg, params, batch, ctx: AxisCtx = AxisCtx()):
    h, aux, _ = forward(cfg, params, batch, ctx)
    loss, cnt = chunked_xent(h, output_head(cfg, params), batch["labels"])
    return loss + aux, {"xent": loss, "aux": aux, "tokens": cnt}


def prefill(cfg, params, batch, ctx: AxisCtx = AxisCtx()):
    """Returns (last-token logits (B, V), cache pytree). Mixed-length
    batches LEFT-pad (prompt ends aligned at index S-1, where the logits
    are read) and pass ``batch["mask"]`` — with the mask the padded forward
    is exact (see ``forward``), without it pad tokens attend."""
    h, _, caches = forward(cfg, params, batch, ctx, return_cache=True)
    with jax.named_scope("lm.head"):
        logits = (h[:, -1].astype(jnp.float32)
                  @ output_head(cfg, params).astype(jnp.float32))
    return logits, caches


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def init_cache(cfg, batch_size: int, seq_len: int, ctx: AxisCtx = AxisCtx(),
               enc_len: int = 0) -> Tuple:
    """Zero cache matching the scan layout: tuple over period positions of
    stacked (n_periods, ...) entries."""
    p = period_of(cfg)
    n_periods = cfg.n_layers // p
    a = cfg.attn
    dt = jnp.dtype(cfg.param_dtype)
    caches = []
    for pos in range(p):
        kind = cfg.layer_kind(pos)
        if kind == "a":
            e = {
                "k": jnp.zeros((n_periods, batch_size, seq_len, a.n_kv_heads,
                                a.head_dim), dt),
                "v": jnp.zeros((n_periods, batch_size, seq_len, a.n_kv_heads,
                                a.head_dim), dt),
            }
            if cfg.n_enc_layers:
                e["xk"] = jnp.zeros((n_periods, batch_size, enc_len,
                                     a.n_kv_heads, a.head_dim), dt)
                e["xv"] = jnp.zeros_like(e["xk"])
        else:
            s = cfg.ssm
            d_in = s.expand * cfg.d_model
            nh = d_in // s.head_dim
            e = {
                "conv": jnp.zeros((n_periods, batch_size, s.conv_width - 1,
                                   d_in + 2 * s.d_state), dt),
                "state": jnp.zeros((n_periods, batch_size, nh, s.d_state,
                                    s.head_dim), jnp.float32),
            }
        caches.append(e)
    return tuple(caches)


def init_paged_cache(cfg, n_slots: int, n_pages: int, page_size: int,
                     ctx: AxisCtx = AxisCtx()) -> Tuple:
    """Paged decode cache: K/V entries are SHARED page pools (n_periods,
    n_pages, page_size, Hkv, hd) — every slot reads/writes through its
    block table — while SSM conv/state stay dense per-slot (they are O(1)
    per request and carry no per-token history). Page 0 is the null page
    (see serving/paged_cache.py)."""
    assert cfg.n_enc_layers == 0, "paged serving: decoder-only models"
    p = period_of(cfg)
    n_periods = cfg.n_layers // p
    a = cfg.attn
    dt = jnp.dtype(cfg.param_dtype)
    caches = []
    for pos in range(p):
        if cfg.layer_kind(pos) == "a":
            e = {
                "k": jnp.zeros((n_periods, n_pages, page_size, a.n_kv_heads,
                                a.head_dim), dt),
                "v": jnp.zeros((n_periods, n_pages, page_size, a.n_kv_heads,
                                a.head_dim), dt),
            }
        else:
            s = cfg.ssm
            d_in = s.expand * cfg.d_model
            nh = d_in // s.head_dim
            e = {
                "conv": jnp.zeros((n_periods, n_slots, s.conv_width - 1,
                                   d_in + 2 * s.d_state), dt),
                "state": jnp.zeros((n_periods, n_slots, nh, s.d_state,
                                    s.head_dim), jnp.float32),
            }
        caches.append(e)
    return tuple(caches)


def decode_step(cfg, params, cache, tokens, t_pos, ctx: AxisCtx = AxisCtx(),
                rope_pos=None, kv_start=None, block_tables=None):
    """tokens: (B, 1) int32; t_pos: () int32 shared position, or (B,) int32
    PER-ROW cache write indices (slot-based decode — every in-flight request
    sits at its own sequence position). rope_pos: optional ()/(B,) RoPE
    positions when they differ from the cache index (left-padded rows);
    kv_start: optional ()/(B,) first valid cache index per row.
    block_tables: optional (B, max_blocks) int32 — the cache's K/V entries
    are then shared paged pools (see ``init_paged_cache``) and each row
    resolves its logical positions through its table.
    Returns (logits (B, V), cache)."""
    Bsz = tokens.shape[0]
    t_vec = jnp.broadcast_to(
        jnp.asarray(t_pos, jnp.int32).reshape(-1), (Bsz,))
    rope_vec = None if rope_pos is None else jnp.broadcast_to(
        jnp.asarray(rope_pos, jnp.int32).reshape(-1), (Bsz,))
    start_vec = None if kv_start is None else jnp.broadcast_to(
        jnp.asarray(kv_start, jnp.int32).reshape(-1), (Bsz,))
    h = embed_tokens(cfg, params, tokens)
    if cfg.n_enc_layers:
        from repro.models.common import sinusoid_at
        pe = jax.vmap(lambda pp: sinusoid_at(pp, cfg.d_model))(t_vec)
        h = h + pe[:, None, :].astype(h.dtype)
    p = period_of(cfg)
    has_cross = cfg.n_enc_layers > 0

    def period_body(x, inp):
        layer_params, cache_in = inp
        new_caches = []
        for pos in range(p):
            x, nc = B.decode_layer(cfg, pos, layer_params[pos], x, ctx,
                                   cache_in[pos], t_vec, has_cross=has_cross,
                                   rope_pos=rope_vec, kv_start=start_vec,
                                   block_table=block_tables)
            new_caches.append(nc)
        return x, tuple(new_caches)

    # the layer loop's own work (slicing each layer's weights and cache out
    # of the stacks, writing its cache back) is scoped; each layer's
    # operations carry their own, innermost, scope as well
    with jax.named_scope("lm.layers"):
        h, new_cache = jax.lax.scan(
            period_body, h, (tuple(params["layers"]), cache))
    with jax.named_scope("lm.head"):
        h = apply_norm(cfg, params["ln_f"], h)
        logits = (h[:, 0].astype(jnp.float32)
                  @ output_head(cfg, params).astype(jnp.float32))
    return logits, new_cache


# ---------------------------------------------------------------------------
# Chunked prefill (continuous-batching admission path)
# ---------------------------------------------------------------------------


def prefill_chunk(cfg, params, cache, tokens, pos_off, valid_len,
                  ctx: AxisCtx = AxisCtx(), slot=None, block_tables=None):
    """Prompt chunks against per-slot cache regions — one admission row or
    a STACK of them (batched chunk admission: several queued requests run
    their chunk step in one compiled call).

    tokens: (A, C) int32, one chunk per admission row (tail-padded when
    valid_len < C); pos_off: ()/(A,) int32 cache index of each row's first
    token; valid_len: ()/(A,) int32 valid tokens per row (0 = the row's
    prompt already ended in this stacked step — pure identity row); slot:
    optional ()/(A,) int32 — when given, ``cache`` is the FULL decode
    cache and each row runs against its own slot (gathered out, updated,
    scattered back), which is how the serving engine stitches prompts into
    per-slot regions with ONE compiled function for every slot set.
    block_tables: optional (A, max_blocks) int32 — the cache's K/V entries
    are then shared paged pools (``init_paged_cache``) written through
    each row's table (SSM conv/state keep the dense per-slot layout).

    The chunk attends over its row's cache up to its own indices (earlier
    chunks included) with exact causal/pad masking, SSM layers scan on
    from the cached (conv window, SSD state) — reset in-graph where
    pos_off == 0, so a freed slot needs no host-side scrubbing before
    reuse. Returns (logits (A, V) at each row's last VALID position,
    updated cache)."""
    assert cfg.n_enc_layers == 0, "chunked prefill: decoder-only models"
    Bc, C = tokens.shape
    pos_off = jnp.broadcast_to(
        jnp.asarray(pos_off, jnp.int32).reshape(-1), (Bc,))
    valid_len = jnp.broadcast_to(
        jnp.asarray(valid_len, jnp.int32).reshape(-1), (Bc,))
    paged = block_tables is not None
    full = cache
    slots = None
    if slot is not None:
        slots = jnp.broadcast_to(jnp.asarray(slot, jnp.int32).reshape(-1),
                                 (Bc,))
        # gather the admission rows: SSM entries always carry a slot axis;
        # K/V only in the contiguous layout (paged pools are shared)
        cache = tuple(
            {k: (v if paged and k in ("k", "v")
                 else jnp.take(v, slots, axis=1))
             for k, v in e.items()} for e in cache)
    # first chunk of a request: the slot's SSM carry must restart from zero
    # (K/V need no reset — stale indices are causal-masked / overwritten)
    first = pos_off == 0

    def _reset(k, v):
        if k not in ("conv", "state"):
            return v
        f = first.reshape((1, -1) + (1,) * (v.ndim - 2))
        return jnp.where(f, jnp.zeros_like(v), v)

    cache = tuple({k: _reset(k, v) for k, v in e.items()} for e in cache)

    h = embed_tokens(cfg, params, tokens)
    q_pos = pos_off[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    mask = jnp.arange(C)[None, :] < valid_len[:, None]
    p = period_of(cfg)

    def period_body(x, inp):
        layer_params, cache_in = inp
        new_caches = []
        for pos in range(p):
            x, nc = B.chunk_layer(cfg, pos, layer_params[pos], x, ctx,
                                  cache_in[pos], pos_off, q_pos, mask,
                                  valid_len, block_table=block_tables)
            new_caches.append(nc)
        return x, tuple(new_caches)

    with jax.named_scope("lm.layers"):       # as in decode_step
        h, new_cache = jax.lax.scan(
            period_body, h, (tuple(params["layers"]), cache))
    with jax.named_scope("lm.head"):
        h = apply_norm(cfg, params["ln_f"], h)
        h_last = jax.vmap(
            lambda hr, vl: jax.lax.dynamic_slice_in_dim(
                hr, jnp.maximum(vl - 1, 0), 1, axis=0))(h, valid_len)[:, 0]
        logits = (h_last.astype(jnp.float32)
                  @ output_head(cfg, params).astype(jnp.float32))
    if slot is not None:
        # scatter the admission rows back (paged K/V pools are already
        # global — the layers updated them directly)
        out = []
        for e_new, e_full in zip(new_cache, full):
            d = {}
            for k, n in e_new.items():
                if paged and k in ("k", "v"):
                    d[k] = n
                else:
                    d[k] = e_full[k].at[:, slots].set(
                        n.astype(e_full[k].dtype))
            out.append(d)
        new_cache = tuple(out)
    return logits, new_cache
