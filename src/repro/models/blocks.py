"""Layer blocks: (attention | mamba) + (dense FFN | MoE), schema + apply,
for train / prefill / decode modes."""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.moe_layer import moe_ffn, moe_schema
from repro.models import attention as A
from repro.models import ssm as S
from repro.models.common import (apply_norm, ffn_apply, ffn_schema,
                                 norm_schema)
from repro.parallel.compat import shard_map
from repro.parallel.mesh import AxisCtx


def _csp(x, ctx: AxisCtx, *axes):
    """Sharding-constraint helper; no-op without a mesh."""
    if not ctx.active:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, P(*axes)))


# ---------------------------------------------------------------------------
# Schema for one layer position
# ---------------------------------------------------------------------------


def layer_schema(cfg, pos: int, ctx: AxisCtx, cross: bool = False) -> Dict:
    kind = cfg.layer_kind(pos)
    s: Dict[str, Any] = {"ln1": norm_schema(cfg, cfg.d_model)}
    if kind == "a":
        s["attn"] = A.attn_schema(cfg, cfg.attn)
        if cross:
            s["ln_x"] = norm_schema(cfg, cfg.d_model)
            s["xattn"] = A.attn_schema(cfg, cfg.attn, cross=True)
    else:
        s["ssm"] = S.ssm_schema(cfg, cfg.ssm)
    has_mlp = cfg.d_ff > 0 or cfg.is_moe_layer(pos)
    if has_mlp:
        s["ln2"] = norm_schema(cfg, cfg.d_model)
        if cfg.is_moe_layer(pos):
            W = ctx.model_size if ctx.active else 1
            s["moe"] = moe_schema(cfg, cfg.moe, W, ctx.etp)
        else:
            s["ffn"] = ffn_schema(cfg, cfg.d_model, cfg.d_ff)
    return s


# ---------------------------------------------------------------------------
# Apply — training / prefill
# ---------------------------------------------------------------------------


def attn_case(ctx: AxisCtx, a, Sq: int) -> str:
    """How attention shards over the model axis. Explicit (not left to the
    SPMD partitioner) because an indivisible head count otherwise makes XLA
    reshard INSIDE the chunked-attention scan loops — one collective per
    (q-block × kv-block) iteration, observed as ~1 TB/device of all-reduce
    on qwen2-0.5b (14 heads on a 16-way axis).

      heads  — Hq and Hkv both divide the axis: classic TP head sharding.
      qheads — only Hq divides: q sharded over heads, K/V replicated once
               per layer (GQA KV is small; Megatron-style).
      seq    — heads don't divide: sequence-parallel attention; K/V
               all-gathered once per layer, q/output stay seq-sharded.
      none   — nothing divides (tiny smoke shapes): replicate.
    """
    m = ctx.model_size
    if not ctx.active or m == 1:
        return "none"
    if a.n_heads % m == 0 and a.n_kv_heads % m == 0:
        return "heads"
    if a.n_heads % m == 0:
        return "qheads"
    if Sq % m == 0 and Sq > 1:
        return "seq"
    return "none"


@jax.named_scope("attn.core")
def _attn_core(a, causal, use_rope, q_sharded, kv_sharded, mx,
               q4, k4, v4, qp, kp, kvm):
    """Local (per-shard) attention body. q4: (B, Sq_l, H_l, hd);
    k4/v4: (B, Sk, Hkv_l, hd); qp/kp: absolute positions (B, Sq_l)/(B, Sk);
    kvm: (B, Sk) kv validity (pad mask) or None.
    Runs under shard_map so fwd AND bwd are collective-free inside."""
    if use_rope:
        q4 = A.apply_rope(q4, qp, a.rope_theta)
        k4 = A.apply_rope(k4, kp, a.rope_theta)
    k_cache, v_cache = k4, v4                    # post-rope, pre-expansion
    H_l, Hkv_l = q4.shape[2], k4.shape[2]
    rep = a.n_heads // a.n_kv_heads
    if mx:
        r = jax.lax.axis_index(mx)
        head_base = r * H_l if q_sharded else 0
        kv_base = r * Hkv_l if kv_sharded else 0
    else:
        head_base = kv_base = 0
    # global q head -> local kv head (works for every sharding case)
    kv_map = (head_base + jnp.arange(H_l)) // rep - kv_base
    ke = jnp.take(k4, kv_map, axis=2)
    ve = jnp.take(v4, kv_map, axis=2)
    with jax.named_scope("__fusable__flash"):
        o = A.attention(q4, ke, ve, causal=causal, q_block=a.q_block,
                        kv_block=a.kv_block, q_pos=qp, kv_pos=kp,
                        kv_mask=kvm)
    return o, k_cache, v_cache


def attn_apply(cfg, p, x, ctx: AxisCtx, positions, causal: bool,
               use_rope: bool = True, kv_x=None, return_kv: bool = False,
               kv_mask=None):
    a = cfg.attn
    src = x if kv_x is None else kv_x
    B, Sq, _ = x.shape
    Sk = src.shape[1]
    with jax.named_scope("attn.qkv"):
        q = x @ p["wq"]
        k = src @ p["wk"]
        v = src @ p["wv"]
        if "bq" in p:
            q = q + p["bq"].astype(q.dtype)
            k = k + p["bk"].astype(k.dtype)
            v = v + p["bv"].astype(v.dtype)
        q = q.reshape(B, Sq, a.n_heads, a.head_dim)
        k = k.reshape(B, Sk, a.n_kv_heads, a.head_dim)
        v = v.reshape(B, Sk, a.n_kv_heads, a.head_dim)
    if positions is None:
        positions = jnp.arange(Sq)[None, :]
    positions = jnp.broadcast_to(positions, (B, Sq))
    kv_positions = (positions if kv_x is None else
                    jnp.broadcast_to(jnp.arange(Sk)[None, :], (B, Sk)))

    Hq_real, Hkv_real = a.n_heads, a.n_kv_heads
    m = ctx.model_size
    padded = (a.pad_heads and ctx.active and m > 1
              and (a.n_heads % m or a.n_kv_heads % m))
    if padded:
        # pad KV heads up to the axis, keep the real group ratio for q
        rep = a.n_heads // a.n_kv_heads
        Hkv_p = -(-a.n_kv_heads // m) * m
        Hq_p = Hkv_p * rep
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Hq_p - a.n_heads), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, Hkv_p - a.n_kv_heads), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, Hkv_p - a.n_kv_heads), (0, 0)))
        # dummy heads: zero K/V ⇒ uniform softmax over zero values ⇒ zero
        # output, and real q head h keeps kv head h//rep < Hkv_real
        a = dataclasses.replace(a, n_heads=Hq_p, n_kv_heads=Hkv_p)

    if kv_mask is not None:
        kv_mask = jnp.broadcast_to(kv_mask, (B, Sk))
    case = attn_case(ctx, a, Sq)
    mx = ctx.model_axis
    if case == "none" or not ctx.active:
        o, kc, vc = _attn_core(a, causal, use_rope, False, False,
                               None, q, k, v, positions, kv_positions,
                               kv_mask)
    else:
        dp = ctx.dp_axes if B % max(1, ctx.dp_size) == 0 else None
        q_sharded = case in ("heads", "qheads")
        kv_sharded = case == "heads"
        q_spec = (P(dp, None, mx, None) if q_sharded
                  else P(dp, mx, None, None))
        kv_spec = (P(dp, None, mx, None) if kv_sharded
                   else P(dp, None, None, None))
        qp_spec = P(dp, None) if q_sharded else P(dp, mx)
        body = partial(_attn_core, a, causal, use_rope, q_sharded,
                       kv_sharded, mx)
        if kv_mask is None:
            body_in = (lambda qq, kk, vv, qp, kp:
                       body(qq, kk, vv, qp, kp, None))
            specs = (q_spec, kv_spec, kv_spec, qp_spec, P(dp, None))
            args = (q, k, v, positions, kv_positions)
        else:
            body_in = body
            specs = (q_spec, kv_spec, kv_spec, qp_spec, P(dp, None),
                     P(dp, None))
            args = (q, k, v, positions, kv_positions, kv_mask)
        o, kc, vc = shard_map(
            body_in, mesh=ctx.mesh,
            in_specs=specs,
            out_specs=(q_spec, kv_spec, kv_spec),
            check_vma=False)(*args)
    if padded:
        # drop dummy-head outputs / cache entries (exact: they are zero)
        o = o[:, :, :Hq_real]
        kc = kc[:, :, :Hkv_real]
        vc = vc[:, :, :Hkv_real]
    o = o.reshape(B, Sq, Hq_real * a.head_dim)
    if ctx.active and case not in ("none",):
        if case == "seq":
            o = _csp(o, ctx, ctx.dp_axes, mx, None)
        else:
            o = _csp(o, ctx, ctx.dp_axes, None, mx)
    out = _attn_out(o, p["wo"])
    if return_kv:
        return out, (kc, vc)
    return out, None


@dataclasses.dataclass(frozen=True)
class ExecSeg:
    """One EXECUTED segment of a block: a closure over an env dict of named
    values, with its dataflow declared (``reads`` / ``writes``) so
    core/schedule.py can derive dependencies and legally reorder emission.
    Reordering only permutes which segment is traced first over identical
    expressions, so any legal order is numerically identical."""
    name: str
    kind: str
    block: int
    reads: Tuple[str, ...]
    writes: Tuple[str, ...]
    fn: Any                     # Callable[[Dict[str, Any]], None]


def block_segments(cfg, pos: int, p, ctx: AxisCtx, positions, enc_out=None,
                   return_cache: bool = False, mask=None, block: int = 0,
                   x_in: str = "x", x_out: str = "x_out"):
    """Lower one layer to its executed segment list. The residual stream
    enters as env[``x_in``] and leaves as env[``x_out``]; internal values
    are namespaced ``L{block}.*`` (aux loss at ``L{block}.aux``, cache
    entry at ``L{block}.cache``). The segment bodies are the EXACT
    expressions of the historical monolithic apply_layer — the lowering
    only names the intermediate values so the scheduler can see, e.g., that
    the MoE shared expert reads the mid residual and is independent of the
    dispatch/combine ring."""
    kind = cfg.layer_kind(pos)
    pr = f"L{block}."
    segs = []
    cross = kind == "a" and enc_out is not None
    xm0 = pr + ("xm0" if cross else "xm")
    xm = xm0

    if kind == "a":
        def f_attn(env):
            h = apply_norm(cfg, p["ln1"], env[x_in])
            h, kv = attn_apply(cfg, p["attn"], h, ctx, positions,
                               cfg.attn.causal, cfg.attn.rope_theta > 0,
                               return_kv=return_cache, kv_mask=mask)
            env[pr + "h0"] = h
            if return_cache:
                env[pr + "cache"] = {"k": kv[0], "v": kv[1]}

        segs.append(ExecSeg(pr + "attn", "attn", block, (x_in,),
                            (pr + "h0",) + ((pr + "cache",)
                                            if return_cache else ()),
                            f_attn))
    else:
        def f_ssm(env):
            h = apply_norm(cfg, p["ln1"], env[x_in])
            h, ssm_cache = S.ssm_forward(cfg, cfg.ssm, p["ssm"], h,
                                         return_cache=return_cache,
                                         mask=mask)
            env[pr + "h0"] = h
            if return_cache:
                env[pr + "cache"] = ssm_cache

        segs.append(ExecSeg(pr + "ssm", "ssm", block, (x_in,),
                            (pr + "h0",) + ((pr + "cache",)
                                            if return_cache else ()),
                            f_ssm))

    def f_res1(env):
        x = env[x_in]
        env[xm0] = x + env[pr + "h0"].astype(x.dtype)

    segs.append(ExecSeg(pr + "res1", "residual", block,
                        (x_in, pr + "h0"), (xm0,), f_res1))

    if cross:
        def f_xattn(env):
            hx = apply_norm(cfg, p["ln_x"], env[pr + "xm0"])
            hx, xkv = attn_apply(cfg, p["xattn"], hx, ctx, positions,
                                 causal=False, use_rope=False,
                                 kv_x=enc_out, return_kv=return_cache)
            env[pr + "hx"] = hx
            if return_cache:
                env[pr + "cache"]["xk"], env[pr + "cache"]["xv"] = xkv

        segs.append(ExecSeg(
            pr + "xattn", "attn", block,
            (pr + "xm0",) + ((pr + "cache",) if return_cache else ()),
            (pr + "hx",) + ((pr + "cache",) if return_cache else ()),
            f_xattn))
        xm = pr + "xm"

        def f_resx(env):
            x = env[pr + "xm0"]
            env[xm] = x + env[pr + "hx"].astype(x.dtype)

        segs.append(ExecSeg(pr + "resx", "residual", block,
                            (pr + "xm0", pr + "hx"), (xm,), f_resx))

    tail_reads = [xm]
    if "ln2" in p:
        if "moe" in p:
            def f_moe(env):
                h = apply_norm(cfg, p["ln2"], env[xm])
                h = _csp(h, ctx, ctx.dp_axes,
                         ctx.model_axis if ctx.seq_shard and h.shape[1] > 1
                         else None, None)
                h, aux = moe_ffn(cfg, cfg.moe, p["moe"], h, ctx,
                                 n_col=cfg.moe.n_col_blocks)
                env[pr + "h1"] = h
                env[pr + "aux"] = aux

            segs.append(ExecSeg(pr + "moe", "moe", block, (xm,),
                                (pr + "h1", pr + "aux"), f_moe))
            if "shared" in p["moe"]:
                # reads the MID residual only — independent of the ring,
                # the one executed segment the scheduler can hoist into it
                def f_shared(env):
                    env[pr + "hsh"] = ffn_apply(
                        cfg, p["moe"]["shared"],
                        apply_norm(cfg, p["ln2"], env[xm]))

                segs.append(ExecSeg(pr + "shared", "shared_ffn", block,
                                    (xm,), (pr + "hsh",), f_shared))
                tail_reads += [pr + "h1", pr + "hsh"]
            else:
                tail_reads += [pr + "h1"]
        else:
            def f_ffn(env):
                env[pr + "h1"] = ffn_apply(
                    cfg, p["ffn"], apply_norm(cfg, p["ln2"], env[xm]))

            segs.append(ExecSeg(pr + "ffn", "ffn", block, (xm,),
                                (pr + "h1",), f_ffn))
            tail_reads += [pr + "h1"]

    def f_tail(env):
        x = env[xm]
        if pr + "h1" in env:
            h = env[pr + "h1"]
            if pr + "hsh" in env:
                h = h + env[pr + "hsh"]
            x = x + h.astype(x.dtype)
        sp = (cfg.sp_residual and ctx.active
              and x.shape[1] % max(1, ctx.model_size) == 0
              and x.shape[1] > 1)
        x = _csp(x, ctx, ctx.dp_axes, ctx.model_axis if sp else None, None)
        env[x_out] = x

    segs.append(ExecSeg(pr + "res2", "residual", block, tuple(tail_reads),
                        (x_out,), f_tail))
    return segs


def run_segments(segs, env):
    """Execute segments in the given emission order against ``env``."""
    for s in segs:
        s.fn(env)
    return env


def apply_layer(cfg, pos: int, p, x, ctx: AxisCtx, positions,
                enc_out=None, return_cache: bool = False, mask=None):
    """Training / prefill path. Returns (x, aux_loss, cache_entry).
    mask: optional (B, S) validity — pad tokens are excluded from attention
    (kv_mask) and become identity steps in the SSM scan, so mixed-length
    left-padded prefill is exact.

    Implemented as the SEQUENTIAL interpretation of ``block_segments`` —
    the same lowering lm.forward_scheduled reorders across blocks."""
    segs = block_segments(cfg, pos, p, ctx, positions, enc_out=enc_out,
                          return_cache=return_cache, mask=mask, block=pos,
                          x_in="x", x_out="x_out")
    env = run_segments(segs, {"x": x})
    aux = env.get(f"L{pos}.aux")
    if aux is None:
        aux = jnp.zeros((), jnp.float32)
    return env["x_out"], aux, env.get(f"L{pos}.cache")


# ---------------------------------------------------------------------------
# Apply — single-token decode with caches
# ---------------------------------------------------------------------------


@jax.named_scope("attn.qkv")
def _qkv_proj(a, p_attn, h):
    """Shared QKV projection + bias + head reshape for the cached paths
    (decode_layer / chunk_layer). h: (B, S, d) -> q/k/v (B, S, H*, hd)."""
    B, S, _ = h.shape
    q = h @ p_attn["wq"]
    k = h @ p_attn["wk"]
    v = h @ p_attn["wv"]
    if "bq" in p_attn:
        q = q + p_attn["bq"].astype(q.dtype)
        k = k + p_attn["bk"].astype(k.dtype)
        v = v + p_attn["bv"].astype(v.dtype)
    q = q.reshape(B, S, a.n_heads, a.head_dim)
    k = k.reshape(B, S, a.n_kv_heads, a.head_dim)
    v = v.reshape(B, S, a.n_kv_heads, a.head_dim)
    return q, k, v


@jax.named_scope("attn.out")
def _attn_out(o, wo):
    """The attention output projection, (..., H*hd) @ (H*hd, d)."""
    return o @ wo


def _mlp_tail(cfg, p, x, ctx: AxisCtx):
    """Shared ln2 → (MoE | FFN) → residual tail for the cached paths."""
    if "ln2" not in p:
        return x
    h = apply_norm(cfg, p["ln2"], x)
    if "moe" in p:
        h, _ = moe_ffn(cfg, cfg.moe, p["moe"], h, ctx)
        if "shared" in p["moe"]:
            h = h + ffn_apply(cfg, p["moe"]["shared"],
                              apply_norm(cfg, p["ln2"], x))
    else:
        h = ffn_apply(cfg, p["ffn"], h)
    return x + h.astype(x.dtype)


@jax.named_scope("attn.core")
def sharded_decode_attention(ctx: AxisCtx, a, q, k_cache, v_cache, t_pos,
                             kv_start=None, block_table=None):
    """Decode attention without gathering the cache. t_pos: () or (B,)
    per-row positions (slot-based decode); kv_start: optional ()/(B,) first
    valid cache index per row (left-padded prefill exclusion).
    block_table: optional (B, nb) int32 — the caches are then shared paged
    pools (n_pages, page, Hkv, hd) and rows read their logical view through
    the table.

    * Hkv divides the model axis → kv-group sharding: q reshaped
      (B,1,Hkv,rep,hd) and sharded with its kv head; zero collectives
      (paged pools shard the SAME way — the Hkv axis — with the block
      table replicated, so the per-shard gather stays local).
    * else S divides → split-KV flash decode: each rank reduces its cache
      shard to (m, l, acc) partials, merged by pmax + two psums of
      (B,H,1[,hd]) — ~kB per layer instead of all-gathering GBs of cache.
    * else → plain replicated decode.
    """
    B = q.shape[0]
    Hkv, hd = k_cache.shape[-2], k_cache.shape[-1]
    m = ctx.model_size
    if not ctx.active or m == 1:
        return A.decode_attention(q, k_cache, v_cache, t_pos, kv_start,
                                  block_table)
    S = k_cache.shape[1] if block_table is None else None
    mx = ctx.model_axis
    dp = ctx.dp_axes if ctx.dp_size > 1 and B % ctx.dp_size == 0 else None
    H = q.shape[2]
    rep = H // Hkv
    # per-row positions travel as explicit shard_map operands (sharded with
    # the batch like the tokens), never as closed-over values
    pos_v = jnp.broadcast_to(jnp.asarray(t_pos, jnp.int32).reshape(-1), (B,))
    start_v = (jnp.zeros((B,), jnp.int32) if kv_start is None else
               jnp.broadcast_to(jnp.asarray(kv_start, jnp.int32).reshape(-1),
                                (B,)))
    if Hkv % m == 0:
        qg = q.reshape(B, 1, Hkv, rep, hd)
        if block_table is not None:
            # paged pools shard on the Hkv axis; the block table rides along
            # replicated and each shard gathers its local head slice
            def body_p(qk, kc, vc, pv, sv, bt):
                qk = qk.reshape(qk.shape[0], 1, -1, hd)
                return A.decode_attention(qk, kc, vc, pv, sv, bt)

            o = shard_map(
                body_p, mesh=ctx.mesh,
                in_specs=(P(dp, None, mx, None, None),
                          P(None, None, mx, None), P(None, None, mx, None),
                          P(dp), P(dp), P(dp, None)),
                out_specs=P(dp, None, mx, None),
                check_vma=False)(qg, k_cache, v_cache, pos_v, start_v,
                                 block_table)
            return o.reshape(B, 1, H, hd)

        def body(qk, kc, vc, pv, sv):
            qk = qk.reshape(qk.shape[0], 1, -1, hd)  # (B_l,1,Hkv_l*rep,hd)
            return A.decode_attention(qk, kc, vc, pv, sv)

        o = shard_map(
            body, mesh=ctx.mesh,
            in_specs=(P(dp, None, mx, None, None),
                      P(dp, None, mx, None), P(dp, None, mx, None),
                      P(dp), P(dp)),
            out_specs=P(dp, None, mx, None),
            check_vma=False)(qg, k_cache, v_cache, pos_v, start_v)
        return o.reshape(B, 1, H, hd)
    if block_table is not None:
        # indivisible heads: paged pools stay replicated (split-KV does not
        # map onto the page pool layout — pages are position-interleaved)
        return A.decode_attention(q, k_cache, v_cache, t_pos, kv_start,
                                  block_table)
    if S % m == 0:
        S_loc = S // m

        def body(qf, kc, vc, pv, sv):
            off = jax.lax.axis_index(mx) * S_loc
            mm, ll, acc = A.decode_attention_partial(qf, kc, vc, pv, off, sv)
            out = A.merge_decode_partials(mm, ll, acc, mx)   # (B,H,1,hd)
            return out.transpose(0, 2, 1, 3).astype(qf.dtype)

        return shard_map(
            body, mesh=ctx.mesh,
            in_specs=(P(dp, None, None, None),
                      P(dp, mx, None, None), P(dp, mx, None, None),
                      P(dp), P(dp)),
            out_specs=P(dp, None, None, None),
            check_vma=False)(q, k_cache, v_cache, pos_v, start_v)
    return A.decode_attention(q, k_cache, v_cache, t_pos, kv_start)


def decode_layer(cfg, pos: int, p, x, ctx: AxisCtx, cache, t_pos,
                 has_cross: bool = False, rope_pos=None, kv_start=None,
                 block_table=None):
    """x: (B, 1, d); cache: layer cache dict; t_pos: () or (B,) int32 cache
    WRITE index per row. rope_pos: optional ()/(B,) RoPE position when it
    differs from the cache index (left-padded rows: real position = index -
    pad offset); kv_start: optional ()/(B,) first valid cache index.
    block_table: optional (B, nb) int32 — K/V cache entries are then shared
    paged pools and reads/writes go through per-row tables.
    Returns (x, new_cache)."""
    kind = cfg.layer_kind(pos)
    a = cfg.attn
    new_cache = dict(cache) if cache is not None else None
    h = apply_norm(cfg, p["ln1"], x)
    if kind == "a":
        B = x.shape[0]
        q, k, v = _qkv_proj(a, p["attn"], h)
        if a.rope_theta > 0:
            rp = t_pos if rope_pos is None else rope_pos
            pos_arr = jnp.broadcast_to(
                jnp.asarray(rp, jnp.int32).reshape((-1, 1)), (B, 1))
            q = A.apply_rope(q, pos_arr, a.rope_theta)
            k = A.apply_rope(k, pos_arr, a.rope_theta)
        if block_table is not None:
            kc, vc = A.paged_update_cache(cache["k"], cache["v"], k, v,
                                          t_pos, block_table)
        else:
            kc, vc = A.update_cache(cache["k"], cache["v"], k, v, t_pos)
        new_cache["k"], new_cache["v"] = kc, vc
        o = sharded_decode_attention(ctx, a, q, kc, vc, t_pos, kv_start,
                                     block_table)
        o = o.reshape(B, 1, a.n_heads * a.head_dim)
        h = _attn_out(o, p["attn"]["wo"])
        x = x + h
        if has_cross:
            hx = apply_norm(cfg, p["ln_x"], x)
            qx = (hx @ p["xattn"]["wq"]).reshape(B, 1, a.n_heads, a.head_dim)
            ox = A.dense_attention(qx, cache["xk"], cache["xv"], causal=False)
            hx = ox.reshape(B, 1, a.n_heads * a.head_dim) @ p["xattn"]["wo"]
            x = x + hx
    else:
        h, ssm_new = S.ssm_forward(cfg, cfg.ssm, p["ssm"], h, cache=cache)
        new_cache = ssm_new
        x = x + h

    return _mlp_tail(cfg, p, x, ctx), new_cache


# ---------------------------------------------------------------------------
# Apply — chunked prefill against a per-slot cache region
# ---------------------------------------------------------------------------


def chunk_layer(cfg, pos: int, p, x, ctx: AxisCtx, cache, pos_off, q_pos,
                mask, valid_len, block_table=None):
    """One prompt CHUNK per admission row against its cache region: x
    (A, C, d) rows enter at cache indices [pos_off[a], pos_off[a] + C);
    queries attend over their OWN row's cache up to their own index
    (previous chunks included), so a prompt split into chunks reproduces
    the monolithic prefill exactly — and A > 1 rows admit several queued
    requests in one stacked call.

    pos_off: (A,) first cache index per row; q_pos: (A, C) absolute cache
    indices of the chunk tokens (index == RoPE position — slot prefill is
    right-anchored at 0); mask: (A, C) token validity (final partial
    chunk's tail AND rows whose prompt already ended in this stacked
    step); valid_len: (A,) valid-token counts. Tail-pad K/V land at
    indices > every valid query's position (causal-masked now, overwritten
    by the first decode steps before any query can reach them), and the
    SSM treats pads as identity steps, so the stitch is exact.
    block_table: optional (A, nb) int32 — K/V entries are then shared
    paged pools; pad/inactive tokens write the null page. Returns
    (x, new_cache)."""
    kind = cfg.layer_kind(pos)
    a = cfg.attn
    new_cache = dict(cache) if cache is not None else None
    h = apply_norm(cfg, p["ln1"], x)
    if kind == "a":
        Bc, C, _ = x.shape
        q, k, v = _qkv_proj(a, p["attn"], h)
        if a.rope_theta > 0:
            q = A.apply_rope(q, q_pos, a.rope_theta)
            k = A.apply_rope(k, q_pos, a.rope_theta)
        if block_table is not None:
            kp, vp = A.paged_chunk_update(cache["k"], cache["v"], k, v,
                                          pos_off, block_table, mask)
            new_cache["k"], new_cache["v"] = kp, vp
        else:
            kc, vc = A.update_cache(cache["k"], cache["v"], k, v, pos_off)
            new_cache["k"], new_cache["v"] = kc, vc
        with jax.named_scope("attn.core"):
            if block_table is not None:
                kc = A.paged_gather(kp, block_table)   # (A, nb*page, Hkv, hd)
                vc = A.paged_gather(vp, block_table)
            S_tot = kc.shape[1]
            kv_pos = jnp.broadcast_to(jnp.arange(S_tot)[None, :],
                                      (Bc, S_tot))
            o = A.attention(q, kc, vc, causal=True, q_block=a.q_block,
                            kv_block=a.kv_block, q_pos=q_pos, kv_pos=kv_pos)
        o = o.reshape(Bc, C, a.n_heads * a.head_dim)
        h = _attn_out(o, p["attn"]["wo"])
        x = x + h.astype(x.dtype)
    else:
        h, ssm_new = S.ssm_forward(cfg, cfg.ssm, p["ssm"], h, cache=cache,
                                   mask=mask, valid_len=valid_len)
        new_cache = ssm_new
        x = x + h.astype(x.dtype)

    return _mlp_tail(cfg, p, x, ctx), new_cache
