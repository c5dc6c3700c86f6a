"""jit-compiled step builders: train (grad-accum, AdamW), prefill, decode.

Each builder returns (jitted_fn, in_shardings, out_shardings, abstract_inputs)
so the dry-run can ``.lower().compile()`` without allocating, and the trainer
can run the identical function for real.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.launch import specs as SP
from repro.models import lm
from repro.models.common import specs_from_schema
from repro.optim.adamw import AdamW
from repro.parallel.mesh import AxisCtx
from repro.parallel.sharding import make_ctx, param_specs

Pytree = Any


def _named(mesh, tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P))


def _with_plan_cache(cfg: ModelConfig, plan_cache: Optional[str],
                     plan_hw: str = "",
                     phase: str = "train") -> ModelConfig:
    """Thread a tuned-plan cache path + latency phase into the MoE config so
    every moe_ffn under this step resolves its transport schedule from the
    phase-qualified cache entry (decode steps get latency-ranked plans,
    prefill chunk-throughput ones, train fwd+bwd)."""
    if not plan_cache or cfg.moe is None:
        return cfg
    return dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, plan_cache=plan_cache,
                                     plan_hw=plan_hw, plan_override=False,
                                     plan_phase=phase))


def state_specs(cfg: ModelConfig, ctx: AxisCtx, fsdp: bool = True):
    schema = lm.model_schema(cfg, ctx)
    pspecs = param_specs(schema, ctx.mesh, fsdp)
    return {
        "params": pspecs,
        "opt": {"m": pspecs, "v": pspecs, "count": P()},
        "step": P(),
    }


def abstract_state(cfg: ModelConfig, ctx: AxisCtx):
    params = lm.abstract_params(cfg, ctx)
    f32 = lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32)
    return {
        "params": params,
        "opt": {"m": jax.tree_util.tree_map(f32, params),
                "v": jax.tree_util.tree_map(f32, params),
                "count": jax.ShapeDtypeStruct((), jnp.int32)},
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }


# ---------------------------------------------------------------------------
# Train
# ---------------------------------------------------------------------------


def make_train_fn(cfg: ModelConfig, ctx: AxisCtx, optim: AdamW, accum: int):
    def loss_fn(params, batch):
        return lm.loss_fn(cfg, params, batch, ctx)

    def step(state, batch):
        params = state["params"]
        if accum > 1:
            def mb(carry, b):
                gsum, lsum = carry
                (lo, met), gr = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, b)
                gsum = jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(jnp.float32), gsum, gr)
                return (gsum, lsum + lo), None
            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, lsum), _ = jax.lax.scan(mb, (zeros, jnp.zeros((), jnp.float32)),
                                            batch)
            grads = jax.tree_util.tree_map(lambda g: g / accum, grads)
            loss = lsum / accum
        else:
            (loss, met), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        new_params, new_opt, stats = optim.update(grads, state["opt"], params)
        # non-finite guard: a NaN/inf loss or grad anywhere (grad_norm
        # covers every leaf) skips the whole update IN-GRAPH — the state is
        # donated, so host-side "don't apply" is not an option. The raw
        # loss still reaches the metrics; the trainer counts skips.
        ok = jnp.isfinite(loss) & jnp.isfinite(stats["grad_norm"])
        keep = lambda n, o: jnp.where(ok, n, o)  # noqa: E731
        new_params = jax.tree_util.tree_map(keep, new_params, params)
        new_opt = jax.tree_util.tree_map(keep, new_opt, state["opt"])
        metrics = {"loss": loss, **stats,
                   "skipped": (1 - ok).astype(jnp.int32)}
        return {"params": new_params, "opt": new_opt,
                "step": state["step"] + ok.astype(state["step"].dtype)}, \
            metrics

    return step


def build_train_step(cfg: ModelConfig, shape: ShapeConfig, mesh: Optional[Mesh],
                     optim: Optional[AdamW] = None, accum: int = 0,
                     fsdp: bool = True, seq_shard: bool = True,
                     plan_cache: Optional[str] = None, plan_hw: str = "",
                     schedule: str = ""):
    """Returns dict with fn/jitted/in_shardings/abstract inputs.

    ``schedule`` sits beside ``plan_cache``: "" keeps the scanned
    layer-at-a-time forward, "sequential"/"overlap" route the step through
    the block-schedule IR (core/schedule.py; layers unroll — see
    ``lm.forward_scheduled``). Numerics are identical either way."""
    cfg = _with_plan_cache(cfg, plan_cache, plan_hw)
    if schedule:
        cfg = dataclasses.replace(cfg, block_schedule=schedule)
    optim = optim or AdamW()
    accum = accum or SP.TRAIN_ACCUM.get(shape.name, 1)
    ctx = make_ctx(cfg, mesh, seq_shard=seq_shard)
    step = make_train_fn(cfg, ctx, optim, accum)
    dp_axes = ctx.dp_axes if ctx.active else ("pod", "data")
    batch_structs, batch_pspecs = SP.train_batch_specs(cfg, shape, accum,
                                                       dp_axes=dp_axes)

    if mesh is None:
        return {"fn": step, "jit": jax.jit(step, donate_argnums=0),
                "batch_structs": batch_structs, "ctx": ctx, "accum": accum,
                "state_abstract": abstract_state(cfg, ctx)}

    sspecs = state_specs(cfg, ctx, fsdp)
    in_sh = (_named(mesh, sspecs), _named(mesh, batch_pspecs))
    out_sh = (_named(mesh, sspecs), None)
    jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                     donate_argnums=0)
    return {"fn": step, "jit": jitted, "batch_structs": batch_structs,
            "state_specs": sspecs, "batch_pspecs": batch_pspecs, "ctx": ctx,
            "accum": accum, "state_abstract": abstract_state(cfg, ctx)}


# ---------------------------------------------------------------------------
# Serve: prefill + decode
# ---------------------------------------------------------------------------


def build_prefill_step(cfg: ModelConfig, shape: ShapeConfig,
                       mesh: Optional[Mesh], fsdp: bool = True,
                       plan_cache: Optional[str] = None, plan_hw: str = ""):
    cfg = _with_plan_cache(cfg, plan_cache, plan_hw, phase="prefill")
    ctx = make_ctx(cfg, mesh, seq_shard=True)

    def fn(params, batch):
        return lm.prefill(cfg, params, batch, ctx)

    batch_structs, batch_pspecs = SP.prefill_batch_specs(
        cfg, shape, dp_axes=ctx.dp_axes if ctx.active else ("pod", "data"))
    params_abs = lm.abstract_params(cfg, ctx)
    if mesh is None:
        return {"fn": fn, "jit": jax.jit(fn), "batch_structs": batch_structs,
                "params_abstract": params_abs, "ctx": ctx}
    schema = lm.model_schema(cfg, ctx)
    pspecs = param_specs(schema, mesh, fsdp)
    in_sh = (_named(mesh, pspecs), _named(mesh, batch_pspecs))
    jitted = jax.jit(fn, in_shardings=in_sh)
    return {"fn": fn, "jit": jitted, "batch_structs": batch_structs,
            "params_abstract": params_abs, "param_pspecs": pspecs, "ctx": ctx}


def build_prefill_chunk_step(cfg: ModelConfig, shape: ShapeConfig,
                             mesh: Optional[Mesh], chunk: int = 0,
                             fsdp: bool = True,
                             plan_cache: Optional[str] = None,
                             plan_hw: str = ""):
    """Chunked-prefill step for the continuous-batching engine: one prompt
    chunk (``chunk`` tokens, batch 1; 0 = min(32, seq_len)) against one
    SLOT of the decode cache described by ``shape`` — the SAME
    (global_batch slots, seq_len cache) geometry as ``build_decode_step``,
    so on a mesh both steps compile identical shardings for the donated
    cache they share. The slot index is a traced argument, so a single
    compiled function admits requests into any slot. Prefill-phase plans
    (chunk-throughput objective) resolve from the cache when threaded in."""
    cfg = _with_plan_cache(cfg, plan_cache, plan_hw, phase="prefill")
    ctx = make_ctx(cfg, mesh, seq_shard=False)
    C = chunk or min(32, shape.seq_len)

    if shape.paged:
        def serve_prefill(params, cache, tokens, pos_off, valid_len, slot,
                          block_tables):
            return lm.prefill_chunk(cfg, params, cache, tokens, pos_off,
                                    valid_len, ctx, slot=slot,
                                    block_tables=block_tables)
    else:
        def serve_prefill(params, cache, tokens, pos_off, valid_len, slot):
            return lm.prefill_chunk(cfg, params, cache, tokens, pos_off,
                                    valid_len, ctx, slot=slot)

    cache_abs, cspecs, _tok, _tok_spec = SP.decode_inputs(cfg, shape, ctx)
    params_abs = lm.abstract_params(cfg, ctx)
    tokens = SP.sds((1, C), jnp.int32)
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    fn = serve_prefill              # its HLO module: jit_serve_prefill
    base = {"fn": fn, "cache_abstract": cache_abs, "tokens": tokens,
            "params_abstract": params_abs, "ctx": ctx, "chunk": C,
            "scalar": scalar}
    if mesh is None:
        base["jit"] = jax.jit(fn, donate_argnums=1)
        return base
    schema = lm.model_schema(cfg, ctx)
    pspecs = param_specs(schema, mesh, fsdp)
    cache_sh = _named(mesh, SP.cache_leaf_specs(cache_abs, cspecs))
    rep = NamedSharding(mesh, P())
    in_sh = (_named(mesh, pspecs), cache_sh,
             NamedSharding(mesh, P(None, None)), rep, rep, rep)
    if shape.paged:
        in_sh = in_sh + (NamedSharding(mesh, P(None, None)),)
    out_sh = (NamedSharding(mesh, P(None, None)), cache_sh)
    base["jit"] = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                          donate_argnums=1)
    base["param_pspecs"] = pspecs
    base["cache_pspecs"] = cspecs
    return base


def build_decode_step(cfg: ModelConfig, shape: ShapeConfig,
                      mesh: Optional[Mesh], fsdp: bool = True,
                      plan_cache: Optional[str] = None, plan_hw: str = ""):
    """Slot-based decode step: per-row positions (every in-flight request at
    its own sequence index), a live-slot mask (retired/free slots emit token
    0 and are ignored by the scheduler), donated cache. Decode-phase plans
    (latency objective) resolve from the cache when one is threaded in."""
    cfg = _with_plan_cache(cfg, plan_cache, plan_hw, phase="decode")
    ctx = make_ctx(cfg, mesh, seq_shard=False)
    B = shape.global_batch

    if shape.paged:
        def serve_decode(params, cache, tokens, pos, live, block_tables):
            logits, new_cache = lm.decode_step(cfg, params, cache, tokens,
                                               pos, ctx,
                                               block_tables=block_tables)
            next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
            next_tok = jnp.where(live[:, None], next_tok, 0)
            return next_tok, logits, new_cache
    else:
        def serve_decode(params, cache, tokens, pos, live):
            logits, new_cache = lm.decode_step(cfg, params, cache, tokens,
                                               pos, ctx)
            next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
            next_tok = jnp.where(live[:, None], next_tok, 0)
            return next_tok, logits, new_cache

    fn = serve_decode               # its HLO module: jit_serve_decode
    cache_abs, cspecs, tok, tok_spec = SP.decode_inputs(cfg, shape, ctx)
    params_abs = lm.abstract_params(cfg, ctx)
    pos = jax.ShapeDtypeStruct((B,), jnp.int32)
    live = jax.ShapeDtypeStruct((B,), jnp.bool_)
    if mesh is None:
        return {"fn": fn, "jit": jax.jit(fn, donate_argnums=1),
                "cache_abstract": cache_abs, "tok": tok,
                "params_abstract": params_abs, "ctx": ctx, "pos": pos,
                "live": live}
    schema = lm.model_schema(cfg, ctx)
    pspecs = param_specs(schema, mesh, fsdp)
    cache_sh = _named(mesh, SP.cache_leaf_specs(cache_abs, cspecs))
    row_spec = NamedSharding(mesh, P(*tok_spec[:1]))
    in_sh = (_named(mesh, pspecs), cache_sh, NamedSharding(mesh, tok_spec),
             row_spec, row_spec)
    if shape.paged:
        in_sh = in_sh + (NamedSharding(mesh, P(tok_spec[0], None)),)
    out_sh = (NamedSharding(mesh, tok_spec), None, cache_sh)
    jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                     donate_argnums=1)
    return {"fn": fn, "jit": jitted, "cache_abstract": cache_abs, "tok": tok,
            "params_abstract": params_abs, "param_pspecs": pspecs,
            "cache_pspecs": cspecs, "ctx": ctx, "pos": pos, "live": live}
