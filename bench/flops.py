"""Operations and bytes the model and its kernels need, computed from the
configuration's shapes (the reference's key names). Model FLOPs count what
the algorithm requires: no recomputation, no capacity padding, no padded
rows. Kernel counts are of what one call executes."""
from __future__ import annotations

from typing import Dict


def layer_matmul_params(m: Dict) -> int:
    """Weights one token multiplies by in one layer: attention
    projections, router and its top-k experts (SwiGLU: three matrices)."""
    d, hd = m["d_model"], m["head_dim"]
    attn = d * (m["n_heads"] + 2 * m["n_kv_heads"]) * hd \
        + m["n_heads"] * hd * d
    router = d * m["num_experts"]
    experts = m["top_k"] * 3 * d * m["d_expert"]
    return attn + router + experts


def head_flops(m: Dict) -> int:
    """One token's logits."""
    return 2 * m["d_model"] * m["vocab_size"]


def attn_flops(m: Dict, context: int) -> int:
    """One query attending over ``context`` keys in every layer (QK^T and
    PV)."""
    return 4 * m["n_layers"] * m["n_heads"] * m["head_dim"] * context


def token_flops(m: Dict, context: int, head: bool = True) -> int:
    """Forward FLOPs of one token at a context of ``context`` keys."""
    return 2 * m["n_layers"] * layer_matmul_params(m) \
        + attn_flops(m, context) + (head_flops(m) if head else 0)


def prefill_flops(m: Dict, prompt_len: int) -> int:
    """A prompt's prefill: every token through every layer with causal
    attention, logits for the last token only."""
    P = prompt_len
    return P * 2 * m["n_layers"] * layer_matmul_params(m) \
        + 4 * m["n_layers"] * m["n_heads"] * m["head_dim"] * P * (P + 1) // 2 \
        + head_flops(m)



# -- kernels -----------------------------------------------------------------
# One call's work from the shapes in its trace event: ``results`` and
# ``operands`` are lists of (dtype, dims, in_hbm) (bench/trace.py
# ``shapes``). Bytes are every HBM operand read once and every HBM result
# written once: what the algorithm needs from HBM, not what a tiling
# re-reads; an array XLA placed in VMEM costs no HBM traffic.

ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
            "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
            "f64": 8}


def nbytes(arrays) -> int:
    total = 0
    for dtype, dims, in_hbm in arrays:
        if not in_hbm:
            continue
        n = ITEMSIZE[dtype]
        for x in dims:
            n *= x
        total += n
    return total


def topk_combine_work(results, operands) -> Dict:
    """``topk_combine``: rows (T, k*d) bf16 and weights (T, k) f32 in,
    (T, d) out; one multiply-add per row element."""
    T, kd = operands[0][1]
    return {"flops": 2 * T * kd, "bytes": nbytes(operands) + nbytes(results)}



def least_time(flops: float, nbytes: float, peak) -> Dict:
    """Roofline-least time of some work, and which bound sets it."""
    tc, tm = flops / peak.flops_bf16, nbytes / peak.hbm_bytes_s
    return {"s": max(tc, tm), "bound": "compute" if tc >= tm else "memory"}
