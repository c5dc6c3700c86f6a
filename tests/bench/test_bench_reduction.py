"""The arithmetic the benchmark reports with: tails over every request,
rates over the whole window, the reduction of a device trace, the peaks
table, and the exit of a run with no TPU."""
import math
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import flops, stats, trace  # noqa: E402
from bench.peaks import PEAKS, UnknownDevice, peak_for  # noqa: E402
from bench.serve import end_to_end  # noqa: E402


def test_percentile_takes_the_tail_of_every_request():
    xs = list(range(1, 101))                       # 1..100
    assert stats.percentile(xs, 50) == pytest.approx(50.5)
    assert stats.percentile(xs, 95) == pytest.approx(95.05)
    # a miss (inf) among the last 5% reaches the tail
    assert stats.percentile(xs[:-1] + [math.inf], 95) == pytest.approx(95.05)
    assert stats.percentile(xs[:-6] + [math.inf] * 6, 95) == math.inf


def test_quartile_spread_matches_statistics_quantiles():
    xs = [10.0, 11.0, 12.0, 13.0, 14.0, 15.0]
    q1, med, q3 = __import__("statistics").quantiles(xs, n=4)
    assert stats.quartile_spread(xs) == pytest.approx((q3 - q1) / med)


def _timeline(stall: float):
    """Two requests due at 0, tokens every 0.1 s for 10 s, with one
    ``stall``-second pause of the whole engine at t=5."""
    emits = {}
    for i in range(2):
        ts, t = [], 0.05
        while t < 10:
            ts.append(t)
            t += 0.1
            if stall and abs(t - 5.05) < 1e-9:
                t += stall
        emits[i] = ts
    return emits


def test_a_stall_lowers_the_rate_and_raises_the_tail():
    due, ok = {0: 0.0, 1: 0.0}, {0: True, 1: True}
    base, *_ = end_to_end(due, ok, _timeline(0), list(_timeline(0).values()),
                          0.0, 10.0)
    em = _timeline(2.0)
    slow, _, gaps, _ = end_to_end(due, ok, em, list(em.values()), 0.0, 10.0)
    assert slow["serve_tokens_per_s"] < base["serve_tokens_per_s"]
    assert base["serve_tokens_per_s"] == pytest.approx(200 / 10.0, rel=0.01)
    assert max(gaps) == pytest.approx(2100.0)
    assert slow["itl_p95_ms"] >= base["itl_p95_ms"]


def test_a_failed_request_is_a_miss():
    due = {i: 0.0 for i in range(20)}
    ok = {i: i != 0 for i in range(20)}
    em = {i: [0.1, 0.2] for i in range(20)}
    e2e, ttft, _, _ = end_to_end(due, ok, em, list(em.values()), 0.0, 1.0)
    assert math.inf in ttft
    assert e2e["ttft_p95_ms"] == math.inf


COMBINE = ("%topk_combine_diff.7 = bf16[512,1536]{1,0:T(8,128)(2,1)S(1)} "
           "custom-call(bf16[512,12288]{1,0:T(8,128)(2,1)S(1)} %fusion.16, "
           "f32[512,8]{1,0:T(8,128)S(1)} %fusion.2), "
           'custom_call_target="tpu_custom_call"')
WGRAD = ("%fused_mlp_wgrad.11 = (bf16[40,1536,512]{2,1,0}, bf16[40,1536,512]"
         "{2,1,0}, bf16[40,512,1536]{2,1,0}) custom-call(bf16[40,512,1536]"
         "{2,1,0} %a, bf16[40,1536,512]{2,1,0} %b, bf16[40,1536,512]{2,1,0} "
         "%c, bf16[40,512,1536]{2,1,0} %d, bf16[40,512,1536]{2,1,0} %e), "
         'custom_call_target="tpu_custom_call"')


def _op(a, b, text):
    return trace.Op(a, b, trace.instruction(text), text)


def _fixture():
    """Two chips over a 100 ns window. TPU:0: compute 0-30, a collective
    20-50 (overlapping compute 20-30), the combine kernel 60-80; TPU:1:
    compute 10-40 only."""
    ops = {"TPU:0": [_op(0, 30, "%fusion.1 = f32[8] fusion(f32[8] %x)"),
                     _op(20, 50, "%collective-permute-done.3 = bf16[4] "
                         "collective-permute-done(bf16[4] %cp)"),
                     _op(60, 80, COMBINE)],
           "TPU:1": [_op(10, 40, "%fusion.2 = f32[8] fusion(f32[8] "
                         "%collective-permute-done.9)")]}
    host = [(0, 100, "traced"), (0, 55, "decode"), (55, 100, "admit")]
    return trace.Reduced(0, 100, ops, host)


def test_trace_reduction_on_a_hand_built_trace():
    r = _fixture()
    assert r.busy_ns("TPU:0") == 70                # 0-50 and 60-80
    assert r.idle_share("TPU:0") == pytest.approx(0.3)
    assert r.idle_share("TPU:1") == pytest.approx(0.7)
    assert r.busy_s == pytest.approx((70 + 30) / 2 * 1e-9)
    assert r.exposed_collective_ns("TPU:0") == 20  # 30-50
    # an operand named after a collective does not make a fusion one
    assert r.exposed_collective_ns("TPU:1") == 0
    assert [(o.start, o.end) for o in r.kernel_ops("TPU:0", "topk_combine")] \
        == [(60, 80)]
    b = r.breakdown()
    assert b["device_ops"][0][0] == "fusion"
    gaps = dict(b["idle_gaps"])
    assert gaps["TPU:1: decode"] == pytest.approx(10e-9)   # 0-10
    assert gaps["TPU:1: admit"] == pytest.approx(60e-9)    # 40-100
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_a_fused_collective_is_a_collective():
    op = _op(0, 1, "%fusion.934 = bf16[8] fusion(bf16[8] %fusion.933), "
             "kind=kCustom, calls=%all-reduce-scatter.clone.clone")
    assert trace.is_collective(op)
    assert not trace.is_collective(_op(0, 1, COMBINE))


def test_kernel_work_from_the_shapes_of_its_trace_event():
    res, ops = trace.shapes(COMBINE)
    # every array of this call sits in VMEM (S(1)): no HBM bytes
    assert res == [("bf16", (512, 1536), False)]
    assert ops == [("bf16", (512, 12288), False), ("f32", (512, 8), False)]
    w = flops.topk_combine_work(res, ops)
    assert w["flops"] == 2 * 512 * 8 * 1536
    assert w["bytes"] == 0
    hbm = [(d, dims, True) for d, dims, _ in ops + res]
    assert flops.nbytes(hbm) == 512 * 12288 * 2 + 512 * 8 * 4 + 512 * 1536 * 2
    # a call with a tuple result, every array in HBM
    res, ops = trace.shapes(WGRAD)
    assert [dims for _, dims, _ in res] == [(40, 1536, 512)] * 2 + \
        [(40, 512, 1536)]
    assert len(ops) == 5 and all(hbm for _, _, hbm in res + ops)


def test_roofline_share_of_a_kernel():
    from bench import readers
    r = _fixture()
    peak = PEAKS["TPU v5 lite"]
    w = flops.topk_combine_work(*trace.shapes(COMBINE))
    run = {"trace": r, "peak": peak}
    share = readers.roofline_share(
        run, "topk_combine",
        lambda op: flops.topk_combine_work(*trace.shapes(op.text)))
    least = flops.least_time(w["flops"], w["bytes"], peak)
    assert least["bound"] == "compute"
    assert share == pytest.approx(100 * least["s"] / 20e-9)


def test_interval_helpers():
    assert trace.union([(5, 8), (0, 3), (2, 4)]) == [(0, 4), (5, 8)]
    assert trace.minus([(0, 10)], [(2, 3), (5, 12)]) == 4
    assert trace.gaps([(2, 3), (5, 6)], 0, 8) == [(0, 2), (3, 5), (6, 8)]


def test_peaks_refuse_an_unknown_device():
    assert peak_for("TPU v5 lite").flops_bf16 == 197e12
    assert peak_for("TPU v5 lite").hbm_bytes_s == 819e9
    with pytest.raises(UnknownDevice):
        peak_for("cpu")


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "granite.serve.chat",
         "--seed", "1", "--seconds", "1", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_run_without_a_tpu_exits_nonzero_with_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not p.stdout.strip()


def test_run_outside_a_checkout_exits_nonzero(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _run(tmp_path)
    assert p.returncode != 0
    assert not p.stdout.strip()
