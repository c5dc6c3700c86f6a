"""RMSNorm Pallas-TPU kernel: row tiles in VMEM, fp32 statistics."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import COMPILER_PARAMS


def _rms_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)                 # (bt, d)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, *, eps: float = 1e-5,
            bt: int = 256, interpret: bool = False) -> jnp.ndarray:
    """x: (T, d); scale: (d,) -> (T, d)."""
    T, d = x.shape
    bt = min(bt, T)
    pad = (bt - T % bt) % bt
    xp = jnp.pad(x, ((0, pad), (0, 0))) if pad else x
    out = pl.pallas_call(
        functools.partial(_rms_kernel, eps=eps),
        grid=((T + pad) // bt,),
        in_specs=[
            pl.BlockSpec((bt, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((bt, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((T + pad, d), x.dtype),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(xp, scale)
    return out[:T] if pad else out
