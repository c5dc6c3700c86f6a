"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def grouped_gemm_ref(lhs, rhs, out_dtype=None):
    """lhs: (E, M, K); rhs: (E, K, N) -> (E, M, N), fp32 accumulation."""
    out = jnp.einsum("emk,ekn->emn", lhs.astype(jnp.float32),
                     rhs.astype(jnp.float32))
    return out.astype(out_dtype or lhs.dtype)


def flash_attention_ref(q, k, v, causal=True):
    """q: (B,Hq,Sq,hd); k/v: (B,Hkv,Sk,hd). fp32 softmax oracle."""
    B, Hq, Sq, hd = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    rep = Hq // Hkv
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / (hd ** 0.5)
    if causal:
        qi = jnp.arange(Sq)[:, None]
        ki = jnp.arange(Sk)[None, :]
        s = jnp.where(ki <= qi, s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", w, v.astype(jnp.float32))
    return out.astype(q.dtype)


def rmsnorm_ref(x, scale, eps=1e-5):
    h = x.astype(jnp.float32)
    var = jnp.mean(h * h, axis=-1, keepdims=True)
    return (h * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
            ).astype(x.dtype)


def fused_mlp_ref(rows, w_gate, w_up, w_down, activation):
    """Unfused oracle for the fused expert-MLP kernel: GEMM1 -> activation ->
    GEMM2 with the hidden materialized, numerics matching the xla backend
    (einsum in the input dtype). rows: (E, R, d) -> (E, R, N)."""
    from repro.models.common import activate
    up = jnp.einsum("erd,edf->erf", rows, w_up)
    if w_gate is not None:
        gate = jnp.einsum("erd,edf->erf", rows, w_gate)
        h = activate(activation, gate, up)
    else:
        h = activate(activation, None, up)
    return jnp.einsum("erf,efn->ern", h.astype(rows.dtype), w_down)


def topk_combine_ref(rows, weights):
    out = jnp.einsum("tkd,tk->td", rows.astype(jnp.float32),
                     weights.astype(jnp.float32))
    return out.astype(rows.dtype)


def ssd_ref(x, dt, A, Bm, Cm, D):
    """Sequential SSD recurrence oracle (== models/ssm.ssd_reference).
    x: (B,S,nh,hd); dt: (B,S,nh); A/D: (nh,); Bm/Cm: (B,S,ds)."""
    from repro.models.ssm import ssd_reference
    return ssd_reference(x, dt, A, Bm, Cm, D)


def paged_decode_attention_ref(q, k_pool, v_pool, pos, block_table,
                               kv_start=None):
    """Paged decode attention by gathering: each row's pages into its
    whole logical view, then one float32 softmax over positions
    ``kv_start..pos`` (the CPU path of ``decode_attention``). q:
    (B, 1, H, hd); pools: (P, page, Hkv, hd); block_table: (B, nb). A free
    slot (null-page table) gets an average of the null page, where the
    kernel returns zeros: compare live rows."""
    from repro.models.attention import decode_attention, paged_gather
    return decode_attention(q, paged_gather(k_pool, block_table),
                            paged_gather(v_pool, block_table), pos, kv_start)
