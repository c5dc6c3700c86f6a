"""The program's own spans and layer scopes on the tiny model of
``tests/bench/tiny.py``: ``repro.obs.Tracer``, the serving engine's spans
and the counters read off them, the named scopes the compiled serving
programs carry, and a CPU profile that puts each decode program's
operations inside its step's call..readback interval."""
import glob
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "tests" / "bench")]

import tiny  # noqa: E402
from bench.program import make_program_params, program_config  # noqa: E402
from repro.obs import Tracer  # noqa: E402
from repro.parallel.mesh import AxisCtx  # noqa: E402
from repro.serving import EngineConfig  # noqa: E402

SEED = 2**33 + 7
# lengths over and under the chunk (16), more requests than slots (4), so
# rounds stack several rows, pad with parking rows and take many chunks
PROMPTS = [[3 + (7 * i + j) % 500 for j in range(n)]
           for i, n in enumerate([5, 40, 17, 3, 33, 9, 21])]
DECODE_CHILDREN = ["serve.decode.inputs", "serve.decode.call",
                   "serve.decode.readback", "serve.decode.finite",
                   "serve.decode.emit"]


def engine(tracer=None):
    """The tiny model served as the benchmark's cell serves it."""
    conf = tiny.conf("granite-3b-a800m.serve1")
    s = conf["serving"]
    ec = EngineConfig(max_seq=s["max_seq"], batch_size=s["slots"],
                      chunk=s["chunk"], page_size=s["page_size"],
                      n_pages=s["n_pages"], admit_k=s["admit_k"],
                      max_restarts=0, recover=False)
    return ec.build(program_config(conf),
                    params=make_program_params(conf, SEED, AxisCtx()),
                    tracer=tracer)


def serve_all(eng):
    rids = [eng.submit(p, max_new=6) for p in PROMPTS]
    eng.run()
    return [eng.finished[r].tokens for r in rids]


@pytest.fixture(scope="module")
def served():
    spans = []
    eng = engine(Tracer(sink=lambda *a: spans.append(a)))
    toks = serve_all(eng)
    return eng, spans, toks


def test_tracer_totals_counts_and_sink():
    got = []
    tr = Tracer(sink=lambda *a: got.append(a))
    with tr.span("a", rows=2):
        with tr.span("a.b"):
            pass
    with tr.span("a", rows=3):
        pass
    with pytest.raises(RuntimeError):
        with tr.span("a", rows=5):
            raise RuntimeError("a span that raises counts nothing")
    assert tr.total("a", "spans") == 2 and tr.total("a", "rows") == 5
    assert tr.total("a", "seconds") > 0 and tr.total("x", "spans") == 0
    assert [g[0] for g in got] == ["a.b", "a", "a"]
    (_, c0, c1, _), (_, t0, t1, counts) = got[0], got[1]
    assert t0 <= c0 <= c1 <= t1 and counts == {"rows": 2}


def inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


def test_engine_spans_nest_and_count(served):
    eng, spans, _ = served
    names = {s[0] for s in spans}
    assert names == {"serve.admit", "serve.admit.chunk", "serve.decode",
                     *DECODE_CHILDREN}
    admits = [s for s in spans if s[0] == "serve.admit"]
    chunks = [s for s in spans if s[0] == "serve.admit.chunk"]
    for a in admits:
        mine = [c for c in chunks if inside(c, a)]
        assert len(mine) == a[3]["chunks"] >= 1
        assert all(c[3]["rows"] == a[3]["rows"] + a[3]["pad_rows"]
                   for c in mine)
        assert sum(c[3]["valid_tokens"] for c in mine) == \
            a[3]["prompt_tokens"]
    assert any(a[3]["pad_rows"] for a in admits)
    assert any(a[3]["chunks"] > 1 for a in admits)
    assert sum(1 for c in chunks if any(inside(c, a) for a in admits)) == \
        len(chunks)
    decodes = [s for s in spans if s[0] == "serve.decode"]
    for d in decodes:
        kids = sorted((s for s in spans if s[0] in DECODE_CHILDREN
                       and inside(s, d)), key=lambda s: s[1])
        assert [k[0] for k in kids] == DECODE_CHILDREN
        assert all(a[2] <= b[1] for a, b in zip(kids, kids[1:]))
        assert 1 <= d[3]["live"] <= d[3]["slots"] == eng.B
    assert len(decodes) == len([s for s in spans
                                if s[0] == "serve.decode.emit"])


def test_engine_counters_are_the_span_totals(served):
    eng, spans, _ = served

    def tot(name, key=None):
        sel = [s for s in spans if s[0] == name]
        return len(sel) if key is None else sum(
            s[2] - s[1] if key == "s" else s[3][key] for s in sel)

    assert eng.admit_rounds == tot("serve.admit")
    assert eng.admissions == tot("serve.admit", "rows") == len(PROMPTS)
    assert eng.prefill_tokens == tot("serve.admit", "prompt_tokens") == \
        sum(map(len, PROMPTS))
    assert eng.decode_steps == tot("serve.decode")
    assert eng.decode_tokens == tot("serve.decode", "live")
    assert eng.prefill_s == pytest.approx(tot("serve.admit", "s"))
    assert eng.decode_s == pytest.approx(tot("serve.decode", "s"))


def test_served_tokens_do_not_depend_on_a_sink(served):
    _, _, toks = served
    plain = serve_all(engine())
    assert plain == toks and all(len(t) == 6 for t in toks)


def test_decode_span_counts_the_kv_pages_read():
    """Each ``serve.decode`` span's ``kv_pages`` is what a layer's paged
    decode attention reads at that step: over the live slots, the pages
    up to the one holding the slot's position (pos // page + 1)."""
    spans = []
    eng = engine(Tracer(sink=lambda *a: spans.append(a)))
    calls, jit = [], eng.decode["jit"]

    def spy(*args):          # params, cache, tokens, pos, live, tables
        calls.append((np.asarray(args[3]), np.asarray(args[4])))
        return jit(*args)

    eng.decode = dict(eng.decode, jit=spy)
    serve_all(eng)
    want = [int((pos[live] // eng.page_size + 1).sum())
            for pos, live in calls]
    got = [s[3]["kv_pages"] for s in spans if s[0] == "serve.decode"]
    assert got == want and len(got) == eng.decode_steps
    # prompts longer than a page: more pages than live rows
    assert eng.decode_kv_pages == sum(want) > eng.decode_tokens


@pytest.fixture(scope="module")
def programs():
    """The tiny model's compiled decode program and a two-row admission
    program, as text."""
    eng = engine()
    args = (eng.params, eng.cache, jnp.zeros((eng.B, 1), jnp.int32),
            jnp.asarray(eng.pos), jnp.asarray(eng.live),
            jnp.asarray(eng.block_tables))
    A, z = 2, jnp.zeros((2,), jnp.int32)
    pre = (eng.params, eng.cache, jnp.zeros((A, eng.chunk), jnp.int32), z,
           z, jnp.arange(A, dtype=jnp.int32),
           jnp.zeros((A, eng.max_blocks), jnp.int32))
    return {"decode": eng.decode["jit"].lower(*args).compile().as_text(),
            "prefill": eng.prefill["jit"].lower(*pre).compile().as_text()}


INSTR = re.compile(r"^\s*(?:ROOT )?%[\w.-]+ = .*?\s([a-z][\w-]*)\(.*"
                   r'op_name="([^"]*)"')
SCOPED_OPS = [("attn.qkv", "dot"), ("attn.cache_write", "scatter"),
              ("attn.core", "dot"), ("attn.out", "dot"),
              ("moe.router", "dot"), ("moe.dispatch", "sort"),
              ("moe.experts", "dot"), ("moe.combine", "gather"),
              ("lm.embed", "gather"), ("lm.head", "dot"),
              ("lm.layers", "dynamic-slice")]


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("scope,opcode", SCOPED_OPS)
def test_compiled_programs_carry_layer_scopes(programs, program, scope,
                                              opcode):
    text = programs[program]
    assert text.startswith(f"HloModule jit_serve_{program},")
    found = [m.groups() for m in map(INSTR.match, text.splitlines()) if m]
    assert any(op == opcode and f"/{scope}/" in name for op, name in found)


def test_decode_ops_run_between_their_steps_call_and_readback(tmp_path):
    """On the CPU the program's host annotations and the XLA runtime's
    operation events share one clock: every operation of the decode
    program lies between its step's ``serve.decode.call`` start and
    ``serve.decode.readback`` end."""
    from jax.profiler import ProfileData

    eng = engine()
    for p in PROMPTS[:3]:
        eng.submit(p, max_new=12)
    for _ in range(3):                       # admitted, compiled, decoding
        eng.step()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(3):
        eng.step()
    jax.profiler.stop_trace()
    pd = ProfileData.from_file(glob.glob(str(tmp_path / "**" /
                                             "*.xplane.pb"),
                                         recursive=True)[0])
    marks, ops = [], []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                t = (e.name, e.start_ns, e.start_ns + e.duration_ns)
                if e.name.startswith("serve.decode"):
                    marks.append(t)
                elif dict(e.stats).get("hlo_module") == "jit_serve_decode":
                    ops.append(t)
    steps = [m for m in marks if m[0] == "serve.decode"]
    assert len(steps) == 3 and ops
    for step in steps:
        call, = [m for m in marks if m[0] == "serve.decode.call"
                 and inside(m, step)]
        back, = [m for m in marks if m[0] == "serve.decode.readback"
                 and inside(m, step)]
        mine = [o for o in ops if step[1] <= o[1] <= step[2]]
        assert mine
        assert all(call[1] <= o[1] and o[2] <= back[2] for o in mine)
    assert all(any(s[1] <= o[1] <= s[2] for s in steps) for o in ops)


def test_disaggregated_workers_span_into_one_sink():
    """A Router gives each worker a tracer of its own (its counters) with
    the sink the Router was built with."""
    from repro.configs.base import get_config

    spans = []
    ec = EngineConfig(max_seq=64, chunk=4, page_size=8, disagg=True,
                      prefill_slots=2, decode_slots=2)
    router = ec.build(get_config("qwen2-0.5b-smoke"),
                      tracer=Tracer(sink=lambda *a: spans.append(a)))
    for p in ([3, 1, 4, 1, 5], [2, 7, 1], [9, 10, 11, 12, 13, 14, 15]):
        router.submit(p, max_new=4)
    router.run()
    tracers = [w.tracer for w in router.workers]
    assert len({id(t) for t in tracers}) == len(tracers)
    assert all(t.sink is tracers[0].sink for t in tracers)
    assert router.prefill_tokens == 15 == sum(
        s[3]["prompt_tokens"] for s in spans if s[0] == "serve.admit")
    assert router.decode_steps == sum(1 for s in spans
                                      if s[0] == "serve.decode")
    assert all(w.decode_steps == 0 for w in router.prefills)
