"""Flash attention (causal, GQA) as a Pallas-TPU kernel.

Grid (B*Hq, Sq/bq, Sk/bk) with the KV dimension innermost; the running
(max, sum, acc) state lives in VMEM scratch and persists across the KV grid
dimension (standard TPU flash pattern). GQA is handled in the K/V BlockSpec
index maps (query head h reads KV head h // (Hq/Hkv)) — no materialized
repeat. Causal masking compares absolute q/k positions; fully-masked KV
blocks are skipped via ``pl.when`` (upper-triangle tiles cost zero MXU work,
the same block-skip the fused CUTLASS kernels in the paper rely on).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import COMPILER_PARAMS

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               bq: int, bk: int, nk: int, scale: float, causal: bool):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    first_q = qi * bq                      # absolute position of this q block
    first_k = ki * bk
    run = (not causal) or (first_k <= first_q + bq - 1)

    @pl.when(run)
    def _block():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                  # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)
        if causal:
            qpos = first_q + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = first_k + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                            # (bq, bk)
        corr = jnp.exp(m_prev - m_new)                    # (bq, 1)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, v_ref[0].astype(jnp.float32),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, bq: int = 128, bk: int = 128,
                    interpret: bool = False) -> jnp.ndarray:
    """q: (B, Hq, Sq, hd); k/v: (B, Hkv, Sk, hd) -> (B, Hq, Sq, hd)."""
    B, Hq, Sq, hd = q.shape
    _, Hkv, Sk, _ = k.shape
    assert Hq % Hkv == 0
    rep = Hq // Hkv
    bq, bk = min(bq, Sq), min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    nq, nk = Sq // bq, Sk // bk

    qr = q.reshape(B * Hq, Sq, hd)
    kr = k.reshape(B * Hkv, Sk, hd)
    vr = v.reshape(B * Hkv, Sk, hd)

    # bh enumerates (b, hq): kv row index = b * Hkv + hq // rep
    def kv_map(bh, qi, ki):
        b = bh // Hq
        h = bh % Hq
        return (b * Hkv + h // rep, ki, 0)

    kernel = functools.partial(
        _fa_kernel, bq=bq, bk=bk, nk=nk,
        scale=1.0 / (hd ** 0.5), causal=causal)
    out = pl.pallas_call(
        kernel,
        grid=(B * Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, hd), kv_map),
            pl.BlockSpec((1, bk, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),     # running max
            pltpu.VMEM((bq, 1), jnp.float32),     # running sum
            pltpu.VMEM((bq, hd), jnp.float32),    # output accumulator
        ],
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(B, Hq, Sq, hd)
