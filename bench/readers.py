"""Arithmetic the per-layer metric readers share (``bench/metrics/``):
spans inside the window, model FLOPs of what the window served, and the
reduced trace. Every reader returns None where its run has nothing to
read."""
from __future__ import annotations

import statistics
from typing import Dict, Optional

from bench import flops


def window_spans(run: Dict, name: str, pred=None):
    w0, w1 = run["window"]
    return run["spans"].within(name, w0, w1, pred)


def mean_span_ms(run: Dict, name: str, pred=None) -> Optional[float]:
    sp = window_spans(run, name, pred)
    if not sp:
        return None
    return sum(s.dur for s in sp) / len(sp) * 1e3


def prompt_len_of(run: Dict) -> Dict[int, int]:
    """Engine request id -> prompt length."""
    return {rid: run["requests"][i].prompt_len
            for i, rid in run["rid_of"].items()}


def prefill_mfu(run: Dict) -> Optional[float]:
    """Model FLOPs of the prompts admitted in the window over admission
    wall time times the chip's peak, in %."""
    sp = window_spans(run, "admit", lambda s: s.info.get("requests"))
    if not sp:
        return None
    plen = prompt_len_of(run)
    work = sum(flops.prefill_flops(run["spec"], plen[r])
               for s in sp for r in s.info["requests"])
    t = sum(s.dur for s in sp)
    return 100.0 * work / (t * run["peak"].flops_bf16)


def decode_mfu(run: Dict) -> Optional[float]:
    """Model FLOPs of the tokens decoded in the window (each at its own
    context length) over decode-step wall time times the peak, in %."""
    plen = prompt_len_of(run)
    decoded: Dict[int, int] = {}
    work, t = 0, 0.0
    w0, w1 = run["window"]
    for s in run["spans"].spans:
        if s.name != "decode" or not s.info.get("rids"):
            continue
        inside = s.t0 >= w0 and s.t1 <= w1
        for rid in s.info["rids"]:
            decoded[rid] = decoded.get(rid, 0) + 1
            if inside:
                # decoded token i reads the prompt and i earlier outputs
                work += flops.token_flops(run["spec"],
                                          plen[rid] + decoded[rid])
        if inside:
            t += s.dur
    if t == 0:
        return None
    return 100.0 * work / (t * run["peak"].flops_bf16)


def trace_of(run: Dict):
    return run.get("trace")


def idle_share(run: Dict) -> Optional[float]:
    """1 - device busy / traced window, in %, the median over chips."""
    tr = trace_of(run)
    if tr is None or not tr.ops:
        return None
    return 100.0 * statistics.median(tr.idle_share(d) for d in tr.ops)


def roofline_share(run: Dict, pattern: str, work) -> Optional[float]:
    """A kernel against its roofline, in %: the roofline-least time of
    each of its calls in the traced window (``work(op)`` gives the
    operations and bytes from the shapes the trace event carries) summed,
    over their summed trace time; the median over chips."""
    tr = trace_of(run)
    if tr is None:
        return None
    shares = []
    for d in tr.ops:
        evs = tr.kernel_ops(d, pattern)
        if not evs:
            continue
        least = 0.0
        for o in evs:
            w = work(o)
            least += flops.least_time(w["flops"], w["bytes"],
                                      run["peak"])["s"]
        shares.append(100.0 * least / (sum(o.end - o.start for o in evs)
                                       * 1e-9))
    return statistics.median(shares) if shares else None
