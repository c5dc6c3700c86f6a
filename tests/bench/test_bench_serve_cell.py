"""The serving cell end to end on the CPU at a cut-down size: the harness's
look for a chip is skipped, the rest of a run is driven as on the chip.
A sound run comes out correct; a token altered where it is produced comes
out not correct; the fp8 control reads wider gaps than the program."""
import io
import json
import math
import sys
import time
from contextlib import redirect_stderr, redirect_stdout
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(Path(__file__).resolve().parent)]

import tiny  # noqa: E402
from bench import cell, serve  # noqa: E402
from bench.peaks import PEAKS  # noqa: E402

SEED = 2**33 + 7


def run(seed=SEED, seconds=2.0, trace=False, on_run=None):
    conf = tiny.conf("granite-3b-a800m.serve1")
    box = {}

    def grab(h):
        box["h"] = h
        if on_run is not None:
            on_run(h)

    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        rc = cell.run_cell("granite.serve.chat", seed, seconds, trace,
                           t_process=time.perf_counter(), conf=conf,
                           mix=tiny.chat_mix(), peak=PEAKS["TPU v5 lite"],
                           on_run=grab, cache=False)
    assert rc == 0
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    return line, box["h"], err.getvalue()


@pytest.fixture(scope="module")
def sound():
    return run()


def test_sound_run_is_correct_and_reports_its_metrics(sound):
    line, h, err = sound
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"itl_p95_ms", "serve_tokens_per_s",
                                    "setup_s"}
    for m in line["metrics"].values():
        assert math.isfinite(m["value"]) and m["value"] > 0
    assert list(line)[-1] == "checks"
    assert list(line["checks"]) == ["widest_logit_gap", "mean_logit_gap"]
    assert err.strip().splitlines()[-1].startswith(
        "check mean_logit_gap = ")
    assert "correct = True" in err


def test_altered_token_is_not_correct():
    line, h, _ = run(on_run=serve.FAULTS["altered_token"])
    assert line["correct"] is False
    assert line["checks"]["widest_logit_gap"]["value"] > \
        line["checks"]["widest_logit_gap"]["limit"]


def test_control_reads_wider_than_the_program(sound):
    """The control, judged by the run's own checks, comes out not
    correct, where the program's run on the same tokens is."""
    _, h, _ = sound
    ctl = serve.control(h)
    assert [c.name for c in ctl.checks] == [c.name for c in
                                             h.outcome.checks]
    for c, p in zip(ctl.checks, h.outcome.checks):
        assert c.limit == p.limit and c.value > p.value
    assert h.outcome.correct is True and ctl.correct is False


def test_traced_run_reads_per_layer_metrics_from_spans():
    line, h, _ = run(seconds=2.0, trace=True)
    m = line["metrics"]
    # the CPU trace has no TPU planes: the device metrics stay silent
    for name in ("admit_ms.serve", "decode_step_ms.serve", "mfu.prefill",
                 "mfu.decode"):
        assert name in m and m[name]["value"] > 0
    assert "idle_share.serve" not in m
