"""The knee of a serving cell: the highest offered rate that the engine
sustains, found once on the chip by a sweep in one process.

    python3 bench/sweep.py --workload granite.serve.chat \\
        --rates 0.15,0.2,0.25,0.3 [--seconds 120] [--settle 30] \\
        [--token-period 0.5] [--seed 1] [--out rows.json]

Each rate starts from the slots at their steady occupancy (the mix's
in-flight requests, ``traffic.inflight``, built with the token period
measured at the rate before), lets the load settle for ``--settle``
seconds, then judges ``--seconds``: the output tokens served against the
tokens offered, the backlog (queued plus live requests, and the output
tokens they still owe) at the start and at the end and over time, and the
time to first token. The knee is the highest rate whose backlog does not
grow and whose requests do not wait for a slot. Between rates every
request is cancelled. Prints one JSON line per rate."""
from __future__ import annotations

import argparse
import json
import sys
import time
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def backlog(eng):
    """(queued + live requests, output tokens they still owe)."""
    live = [r for r in eng.slot_req if r is not None]
    owed = sum(r.max_new for r in eng.queue) + \
        sum(r.max_new - len(r.tokens) for r in live)
    return len(eng.queue) + len(live), owed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=120.0)
    ap.add_argument("--settle", type=float, default=30.0)
    ap.add_argument("--token-period", type=float, default=0.5,
                    help="seconds between two tokens of a decoding request,"
                         " for the first rate's in-flight requests")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default="", help="also write the rows here")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import cell, harness, serve, stats, traffic
    from bench.program import load_config, model_spec

    cell.enable_cache()
    harness.devices(1)
    bm = harness.load_benchmark()
    w = harness.find_cell(bm, args.workload)
    conf, mix = load_config(w["config"]), traffic.load(w["traffic"])
    vocab = model_spec(conf)["vocab_size"]
    clock = time.perf_counter
    emits = defaultdict(list)
    eng = serve.build_engine(conf, args.seed,
                             lambda rid, i, t: emits[rid].append(clock()))
    serve.warm_up(eng, vocab)
    tau = args.token_period
    rows = []
    for rate in [float(r) for r in args.rates.split(",")]:
        m = dict(mix, arrival={"process": "poisson", "rate_rps": rate},
                 steady_start={"token_period_s": tau},
                 warmup_s=args.settle, drain_s=0.0)
        sched = traffic.open_loop(m, args.seed, args.seconds)
        start = clock()
        w0, w1 = start + sched.window_start, start + sched.window_end
        due = [start + r.due for r in sched.requests]
        rids, nxt, trace = {}, 0, []
        at_w0 = steps_w0 = None
        while True:
            now = clock()
            if at_w0 is None and now >= w0:
                at_w0, steps_w0 = backlog(eng), eng.decode_steps
            if now >= w1:
                break
            while nxt < len(due) and due[nxt] <= now:
                q = sched.requests[nxt]
                rids[nxt] = eng.submit(sched.prompt(nxt, vocab),
                                       max_new=q.max_new)
                nxt += 1
            trace.append((round(now - start, 2),) + backlog(eng))
            if eng.pending:
                eng.step()
            else:
                time.sleep(0.005)
        at_w1, steps = backlog(eng), eng.decode_steps - steps_w0
        in_w = [i for i, r in enumerate(sched.requests) if r.in_window]
        offered = sum(sched.requests[i].max_new for i in in_w)
        served = sum(1 for ts in emits.values() for t in ts if w0 <= t <= w1)
        ttft = [(emits[rids[i]][0] - due[i]) * 1e3 for i in in_w
                if i in rids and emits[rids[i]]]
        period = (w1 - w0) / steps if steps else None
        row = {"rate_rps": rate, "token_period_in_s": tau,
               "inflight_at_start": sum(1 for r in sched.requests
                                        if r.due == 0.0),
               "window_requests": len(in_w),
               "window_with_first_token": len(ttft),
               "offered_tokens_per_s": offered / (w1 - w0),
               "served_tokens_per_s": served / (w1 - w0),
               "backlog_start": at_w0[0], "backlog_end": at_w1[0],
               "owed_tokens_start": at_w0[1], "owed_tokens_end": at_w1[1],
               "token_period_s": period, "decode_steps": steps,
               "ttft_p50_ms": stats.percentile(ttft, 50) if ttft else None,
               "ttft_p95_ms": stats.percentile(ttft, 95) if ttft else None,
               "trace": trace[::max(1, len(trace) // 40)]}
        print(json.dumps(row), flush=True)
        rows.append(row)
        if period:
            tau = period
        for r in list(eng.queue) + [r for r in eng.slot_req
                                    if r is not None]:
            eng.cancel(r.rid)
        emits.clear()
    if args.out:
        Path(args.out).write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
