"""The serving cells: an open loop of requests into the program's
``ServeEngine`` (``EngineConfig.build`` -> ``submit`` / ``step``), timed
from each request's due time, then the tokens served to every request
compared with the plain reference."""
from __future__ import annotations

import gc
import math
import time
from collections import defaultdict
from typing import Dict, List

import numpy as np

from bench import stats, traffic
from bench.harness import Check, Outcome
from bench.reference import check_serve
from bench.spans import Recorder


def build_engine(conf: Dict, seed: int, on_token):
    from repro.parallel.mesh import AxisCtx
    from repro.serving import EngineConfig

    from bench.program import make_program_params, program_config

    cfg = program_config(conf)
    params = make_program_params(conf, seed, AxisCtx())
    s = conf["serving"]
    ec = EngineConfig(max_seq=s["max_seq"], batch_size=s["slots"],
                      chunk=s["chunk"], page_size=s["page_size"],
                      n_pages=s["n_pages"], admit_k=s.get("admit_k", 0),
                      max_restarts=0, recover=False)
    return ec.build(cfg, params=params, on_token=on_token)


def instrument(eng, rec: Recorder):
    """Benchmark spans around the engine's two phases (the engine's own
    ``step`` still drives them)."""
    prefill_step, decode_step = eng.prefill_step, eng.decode_step

    def admit():
        with rec.span("admit") as sp:
            pairs = prefill_step()
            sp.info["requests"] = [r.rid for _, r in pairs]
        return pairs

    def decode():
        live = [r.rid for r in eng.slot_req if r is not None]
        with rec.span("decode") as sp:
            n = decode_step()
            sp.info["rows"] = n
            sp.info["rids"] = live
        return n

    eng.prefill_step, eng.decode_step = admit, decode


def warm_up(eng, vocab: int):
    """Every program this cell's traffic uses: the admission program for
    each row count the engine can stack (1 up to ``admit_k``; a round pads
    to a power of two only while free slots are left to pad with), and
    the decode program. The admission programs are called directly with
    empty rows (every write lands in the null page); then two requests
    go through the engine's own API."""
    import jax.numpy as jnp

    k = eng.admit_k or eng.B
    C = eng.chunk
    for A in range(1, k + 1):
        z = jnp.zeros((A,), jnp.int32)
        args = (eng.params, eng.cache, jnp.zeros((A, C), jnp.int32), z, z,
                jnp.arange(A, dtype=jnp.int32))
        if eng.paged:
            args += (jnp.zeros((A, eng.max_blocks), jnp.int32),)
        logits, eng.cache = eng.prefill["jit"](*args)
        np.asarray(jnp.argmax(logits, axis=-1))
        np.asarray(jnp.isfinite(logits).all(axis=-1))
    for i in range(2):
        eng.submit([1 + i] * 8, max_new=2)
    while eng.pending:
        eng.step()
    eng.finished.clear()


def run(h) -> Outcome:
    """``h``: the run context (``bench/run.py``)."""
    conf, mix, seed, seconds = h.conf, h.mix, h.seed, h.seconds
    m = h.spec
    clock = time.perf_counter
    emits = defaultdict(list)                    # engine rid -> [t]
    eng = build_engine(conf, seed, lambda rid, idx, tok:
                       emits[rid].append(clock()))
    warm_up(eng, m["vocab_size"])
    if h.on_engine is not None:
        h.on_engine(eng)
    emits.clear()
    rec = Recorder()
    instrument(eng, rec)
    sched = traffic.open_loop(mix, seed, seconds)
    n = len(sched.requests)
    rid_of: Dict[int, int] = {}                   # schedule index -> rid
    start = clock() + 0.05
    w0, w1 = start + sched.window_start, start + sched.window_end
    drain_end = w1 + float(mix["drain_s"])
    due = [start + r.due for r in sched.requests]
    nxt = 0
    in_window = [i for i, r in enumerate(sched.requests) if r.in_window]
    tracer = h.tracer(rec) if h.trace else None
    t_window = None
    while True:
        now = clock()
        if t_window is None and now >= w0:
            t_window = now
            h.mark_window_start()
        if now >= w1 and h.compiles.on:
            h.mark_window_end()
        if tracer is not None:
            tracer.poll(now, w0, w1)
        while nxt < n and due[nxt] <= now:
            q = sched.requests[nxt]
            with rec.span("submit"):
                rid_of[nxt] = eng.submit(sched.prompt(nxt, m["vocab_size"]),
                                         max_new=q.max_new)
            nxt += 1
        if now >= w1 and all(emits.get(rid_of.get(i)) or
                             rid_of.get(i) in eng.finished
                             for i in in_window):
            break
        if now >= drain_end:
            h.mark_window_end()
            break
        if eng.pending:
            eng.step()
        else:
            wait = (due[nxt] - now) if nxt < n else 0.01
            with rec.span("wait_arrival"):
                time.sleep(max(0.0, min(wait, 0.01)))
    if tracer is not None:
        tracer.stop_if_running()
    # --- end-to-end metrics, from the host clock -------------------------
    recs = {i: eng.finished.get(rid_of.get(i)) for i in in_window}
    # a request still decoding when its first token is in counts as
    # served for the tail; one that ended in any state but ok does not
    ok = {i: bool(emits.get(rid_of.get(i))) and
          (r is None or r.status.value == "ok") for i, r in recs.items()}
    e2e, ttft, gaps, toks = end_to_end(
        {i: due[i] for i in in_window}, ok,
        {i: emits.get(rid_of.get(i), []) for i in in_window},
        list(emits.values()), w0, w1)
    failed = sum(1 for v in ok.values() if not v)
    errors = sum(1 for r in recs.values() if r is not None
                 and r.status.value in ("failed", "quarantined"))
    # the check compares every request the timed path served tokens to:
    # those that finished, and those still decoding with the tokens served
    # so far (a fault confined to one slot can sit on a request that
    # outlives the run, which no sample of finished requests would see)
    live = {r.rid: r for r in eng.slot_req if r is not None}
    served: Dict[int, List[int]] = {}
    n_done = 0
    for i, rid in rid_of.items():
        r = eng.finished.get(rid)
        if r is not None and r.status.value == "ok":
            served[i] = list(r.tokens)
            n_done += 1
        elif rid in live and live[rid].tokens:
            served[i] = list(live[rid].tokens)
    run_info = {"spans": rec, "window": (w0, w1), "ref": h.ref, "spec": m,
                "requests": sched.requests, "rid_of": rid_of,
                "emits": emits, "peak": h.peak, "chips": h.chips,
                "trace": tracer.reduced if tracer is not None else None,
                "ttft_ms": ttft, "itl_ms": gaps}
    notes = [f"window {w1 - w0:.3f} s: {len(in_window)} requests due, "
             f"{failed} failed or without a first token, {n_done} "
             f"finished in all, {toks} tokens, ttft p95 "
             f"{e2e['ttft_p95_ms']:.1f} ms (reported, not bounded), "
             f"{len(gaps)} gaps; {n} requests offered in all"]
    # --- memory, then free the program before the reference --------------
    h.read_memory()
    del eng, live
    gc.collect()
    items = [(sched.prompt(i, m["vocab_size"]), served[i])
             for i in sorted(served)]
    run_info["check_items"] = items
    gap = check_serve.served_gaps(h.ref, h.spec, conf["init"], seed, items)
    notes.append(f"reference: {len(items)} requests ({n_done} finished), "
                 f"{gap_profile(gap['gaps'])}")
    checks = gap_checks(gap["gaps"], conf["check"])
    notes.append(f"{errors} requests ended failed or quarantined")
    return Outcome(attempted=len(in_window), failed=failed,
                   end_to_end=e2e, checks=checks, run=run_info,
                   ok=errors == 0 and bool(items), notes=notes)


def end_to_end(due: Dict[int, float], ok: Dict[int, bool],
               emits: Dict[int, List[float]], all_emits, w0: float,
               w1: float):
    """The serving metrics of a window [w0, w1] on the host clock.
    ``due``/``ok``/``emits``: per request due in the window, its due time,
    whether it finished ok, and the times its tokens were emitted;
    ``all_emits``: the emission times of every request that ran. Time to
    first token counts from the due time, a failed request or one with no
    token counts as a miss (inf); inter-token gaps are every gap between
    consecutive tokens of one request that ends inside the window, pooled;
    the rate is the tokens emitted inside the window over its length."""
    ttft = [(emits[i][0] - due[i]) * 1e3 if ok[i] and emits[i] else math.inf
            for i in due]
    gaps = [(b - a) * 1e3 for ts in all_emits for a, b in zip(ts, ts[1:])
            if w0 <= b <= w1]
    toks = sum(1 for ts in all_emits for t in ts if w0 <= t <= w1)
    e2e = {"ttft_p95_ms": stats.percentile(ttft, 95) if ttft else math.inf,
           "itl_p95_ms": stats.percentile(gaps, 95) if gaps else math.inf,
           "serve_tokens_per_s": toks / (w1 - w0)}
    return e2e, ttft, gaps, toks


def gap_checks(gaps, limits: Dict) -> List[Check]:
    """The numbers ``correct`` compares, from the gap of every served
    token: the widest (one token altered where it is produced reads ~4)
    and the mean (a lower precision moves many tokens a little; its widest
    gap over ~3,000 tokens is no wider than a sound run's rarest)."""
    n = len(gaps)
    return [Check("widest_logit_gap", max(gaps) if n else math.nan,
                  limits["widest_logit_gap_limit"]),
            Check("mean_logit_gap", sum(gaps) / n if n else math.nan,
                  limits["mean_logit_gap_limit"])]


def gap_profile(gaps) -> str:
    """How a run's per-token gaps lie: count, how many are not 0, mean,
    and the ten widest."""
    g = sorted(gaps, reverse=True)
    nz = sum(1 for x in g if x > 1e-6)
    mean = sum(g) / len(g) if g else math.nan
    return (f"gaps: {len(g)} tokens, {nz} above 0, mean {mean!r}, widest "
            f"{[round(x, 5) for x in g[:10]]}")


def control(h) -> Outcome:
    """The control, judged as a run is: the plain reference computed in
    fp8 put in the program's place, at each position of the prompts and
    served tokens that this run's check compared; its gaps are those, under
    the float32 reference, of the token the fp8 reference puts first,
    against the configuration's limits."""
    items = h.outcome.run["check_items"]
    g = check_serve.served_gaps(h.ref, h.spec, h.conf["init"], h.seed,
                                items, quant="fp8")
    checks = gap_checks(g.get("control_gaps", []), h.conf["check"])
    return Outcome(attempted=h.outcome.attempted, failed=0, end_to_end={},
                   checks=checks, ok=bool(items),
                   notes=[f"control over {g['tokens']} served tokens; the "
                          f"program's own gap there {g['gap']!r}; control "
                          f"{gap_profile(g.get('control_gaps', []))}"])


def plant_altered_token(eng):
    """Fault: on every decode call, the first live row's next token is
    replaced by another id where it is produced."""
    import jax.numpy as jnp
    fn = eng.decode["jit"]

    def call(*args):
        nxt, logits, cache = fn(*args)
        row = int(jnp.argmax(args[4]))
        nxt = nxt.at[row, 0].set((nxt[row, 0] + 1) % logits.shape[-1])
        return nxt, logits, cache

    eng.decode["jit"] = call


def _altered_token(h):
    h.on_engine = plant_altered_token


# the faults a serving cell can have, each planted in a run's context
# before the run (``bench/calibrate.py``, the CPU tests)
FAULTS = {"altered_token": _altered_token}
