"""granite's tiny CPU cell reads as the harness read it before it looked
architectures up by name (``pinned_granite_tiny.json``, recorded from that
harness): on two seeds the same weights bit for bit, the same served
tokens, the same gap for every served token, for the fp8 control and for
every token altered, and the same model FLOPs at a few lengths."""
import hashlib
import io
import json
import sys
import time
from contextlib import redirect_stderr, redirect_stdout
from pathlib import Path

import jax
import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT), str(HERE)]

import tiny  # noqa: E402
from bench import cell  # noqa: E402
from bench.peaks import PEAKS  # noqa: E402
from bench.program import (load_config, make_program_params,  # noqa: E402
                           reference_of)
from bench.reference import check_serve  # noqa: E402

PIN = json.loads((HERE / "pinned_granite_tiny.json").read_text())
SEEDS = sorted(PIN["seeds"])
CONF = "granite-3b-a800m.serve1"


def digest(tree) -> str:
    h = hashlib.sha256()
    for path, x in sorted(jax.tree_util.tree_flatten_with_path(tree)[0],
                          key=lambda p: jax.tree_util.keystr(p[0])):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(x).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("seed", SEEDS)
def test_weights_bit_for_bit(seed):
    from repro.parallel.mesh import AxisCtx
    params = make_program_params(tiny.conf(CONF), int(seed), AxisCtx())
    assert digest(params) == PIN["seeds"][seed]["params_sha256"]


@pytest.mark.parametrize("seed", SEEDS)
def test_served_tokens(seed):
    """The requests served in both runs get the same tokens: equal up to
    the shorter, since a request still decoding at the end is compared
    with the tokens it has so far."""
    box = {}
    with redirect_stdout(io.StringIO()), redirect_stderr(io.StringIO()):
        cell.run_cell("granite.serve.chat", int(seed), 2.0, False,
                      t_process=time.perf_counter(), conf=tiny.conf(CONF),
                      mix=tiny.chat_mix(), peak=PEAKS["TPU v5 lite"],
                      on_run=lambda h: box.setdefault("h", h), cache=False)
    got = {tuple(p): list(s)
           for p, s in box["h"].outcome.run["check_items"]}
    want = {tuple(p): s for p, s in PIN["seeds"][seed]["items"]}
    both = set(got) & set(want)
    assert len(both) >= len(want) // 2
    compared = 0
    for p in both:
        n = min(len(got[p]), len(want[p]))
        assert got[p][:n] == want[p][:n]
        compared += n
    assert compared >= sum(len(s) for s in want.values()) // 2


@pytest.mark.parametrize("seed", SEEDS)
def test_every_gap(seed):
    """The reference over the pinned prompts and served tokens, in float32
    and in the fp8 control, and over the same tokens each altered by one
    id: every token's gap as it was."""
    conf = tiny.conf(CONF)
    ref = reference_of(conf)
    m = ref.spec(conf)
    pin = PIN["seeds"][seed]
    items = [(p, s) for p, s in pin["items"]]
    g = check_serve.served_gaps(ref, m, conf["init"], int(seed), items,
                                quant="fp8")
    assert g["gaps"] == pin["gaps"]
    assert g["control_gaps"] == pin["control_gaps"]
    V = m["vocab_size"]
    alt = [(p, [(t + 1) % V for t in s]) for p, s in items]
    ga = check_serve.served_gaps(ref, m, conf["init"], int(seed), alt)
    assert ga["gaps"] == pin["altered_gaps"]


@pytest.mark.parametrize("size", ["full", "tiny"])
def test_model_flops(size):
    conf = load_config(CONF) if size == "full" else tiny.conf(CONF)
    ref = reference_of(conf)
    m = ref.spec(conf)
    want = PIN["flops"][size]
    assert {n: ref.prefill_flops(m, int(n))
            for n in want["prefill_flops"]} == want["prefill_flops"]
    assert {n: ref.token_flops(m, int(n))
            for n in want["token_flops"]} == want["token_flops"]
