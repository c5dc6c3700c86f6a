"""Operations and bytes the kernels need, from the shapes in their trace
events, and the roofline-least time of some work. Kernel counts are of
what one call executes. Model FLOPs are each architecture's own, in its
plain reference (``token_flops``, ``prefill_flops``)."""
from __future__ import annotations

from typing import Dict


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
