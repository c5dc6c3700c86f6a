"""Each architecture is files found by its configuration's ``reference``
name: a plain reference, an adapter to the program and a tiny overlay.
Every configuration of the benchmark resolves and conforms; and a
test-only architecture with layers of two kinds (``lead_dense_moe``: a
leading dense layer, then MoE layers with a shared expert), injected by
name, runs through ``run_cell`` on the CPU from its own files alone."""
import dataclasses
import io
import json
import sys
import time
from contextlib import redirect_stderr, redirect_stdout
from pathlib import Path

import jax
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT), str(HERE)]

import adapter_lead_dense_moe  # noqa: E402
import ref_lead_dense_moe  # noqa: E402
import tiny  # noqa: E402
from bench import cell, program, serve  # noqa: E402
from bench.peaks import PEAKS  # noqa: E402

BM = json.loads((ROOT / "BENCHMARK.json").read_text())
SEED = 2**33 + 7
REFERENCE = ("spec", "layer_key", "top_key", "top_weights", "layer_weights",
             "embed", "layer", "logits", "token_flops", "prefill_flops")
ADAPTER = ("check", "build")


@pytest.fixture(scope="module", autouse=True)
def lead_dense_moe():
    """The test-only architecture, found by its name as a benchmark
    architecture is."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(sys.modules, "bench.reference.lead_dense_moe",
                   ref_lead_dense_moe)
        mp.setitem(sys.modules, "bench.adapters.lead_dense_moe",
                   adapter_lead_dense_moe)
        yield


def full_conf(name):
    if name == "lead_dense_moe":
        return json.loads((HERE / "lead_dense_moe.json").read_text())
    return program.load_config(name)


CONFS = [c["name"] for c in BM["configs"]] + ["lead_dense_moe"]


@pytest.mark.parametrize("name", CONFS)
def test_architecture_resolves_by_name(name):
    conf = full_conf(name)
    ref, adapter = program.reference_of(conf), program.adapter_of(conf)
    for f in REFERENCE:
        assert callable(getattr(ref, f)), (conf["reference"], f)
    for f in ADAPTER:
        assert callable(getattr(adapter, f)), (conf["reference"], f)


@pytest.mark.parametrize("name", CONFS)
def test_spec_is_built_from_the_file(name):
    conf = full_conf(name)
    ref = program.reference_of(conf)
    m = ref.spec(conf)
    assert m["n_layers"] == conf["num_hidden_layers"]
    assert m["vocab_size"] == conf["vocab_size"]
    hash(m)                          # a static argument of the reference
    other = dict(conf, vocab_size=conf["vocab_size"] + 1)
    assert ref.spec(other)["vocab_size"] == conf["vocab_size"] + 1


@pytest.mark.parametrize("name", CONFS)
def test_check_passes_and_catches_a_departure(name):
    conf = full_conf(name)
    cfg = program.program_config(conf)
    assert cfg.n_layers == conf["num_hidden_layers"]
    ref, adapter = program.reference_of(conf), program.adapter_of(conf)
    wider = dataclasses.replace(cfg, vocab_size=cfg.vocab_size + 1)
    assert "vocab_size" in adapter.check(wider, ref.spec(conf))


@pytest.mark.parametrize("name", CONFS)
def test_tiny_seeded_weights_map_onto_the_program(name):
    from repro.models import lm
    from repro.parallel.mesh import AxisCtx

    conf = tiny.shrink(full_conf(name))
    params = program.make_program_params(conf, SEED, AxisCtx())
    want = lm.abstract_params(program.program_config(conf), AxisCtx())
    sig = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda x: (x.shape, x.dtype), t)
    assert sig(params) == sig(want)


def run(seed=SEED, on_run=None):
    conf = tiny.shrink(full_conf("lead_dense_moe"))
    box = {}

    def grab(h):
        box["h"] = h
        if on_run is not None:
            on_run(h)

    out = io.StringIO()
    with redirect_stdout(out), redirect_stderr(io.StringIO()):
        rc = cell.run_cell("granite.serve.chat", seed, 2.0, False,
                           t_process=time.perf_counter(), conf=conf,
                           mix=tiny.chat_mix(), peak=PEAKS["TPU v5 lite"],
                           on_run=grab, cache=False)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1]), box["h"]


@pytest.fixture(scope="module")
def sound():
    return run()


def test_second_architecture_sound_run_is_correct(sound):
    line, h = sound
    assert h.ref is ref_lead_dense_moe
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"itl_p95_ms", "serve_tokens_per_s",
                                    "setup_s"}


def test_second_architecture_altered_token_is_not_correct():
    line, _ = run(on_run=serve.FAULTS["altered_token"])
    assert line["correct"] is False
    assert line["checks"]["widest_logit_gap"]["value"] > \
        line["checks"]["widest_logit_gap"]["limit"]


def test_second_architecture_control_reads_wider_than_the_program(sound):
    _, h = sound
    ctl = serve.control(h)
    for c, p in zip(ctl.checks, h.outcome.checks):
        assert c.limit == p.limit and c.value > p.value
    assert h.outcome.correct is True and ctl.correct is False
