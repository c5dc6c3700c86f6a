"""Readings that set the limits of ``correct`` (not run by the benchmark's
own runs): for each seed, in one process, the program's numbers, the
control's (the plain reference computed in the precision below the
configuration's, in the program's place) and the numbers of the faults a
cell can have, planted in the program. Each is judged against the
configuration's limits as a run is, so a control or a fault that the
check catches comes out ``"correct": false``.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 \\
        [--control-seeds 1,2,3] [--fault-seeds 1,2,3] [--seconds 8]

The cell's entry module (``bench/<entry>.py``) supplies ``control(run)``
and ``FAULTS``. Prints one JSON line per reading, and with ``--out
<file>`` also writes them all there.
"""
from __future__ import annotations

import argparse
import io
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def ints(s: str):
    return [int(x) for x in s.split(",") if x]


def record(seed, kind, outcome, wall_s):
    return {"seed": seed, "kind": kind,
            "checks": {c.name: {"value": c.value, "limit": c.limit}
                       for c in outcome.checks},
            "correct": outcome.correct, "notes": outcome.notes,
            "wall_s": wall_s}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=ints, default=[])
    ap.add_argument("--control-seeds", type=ints, default=[])
    ap.add_argument("--fault-seeds", type=ints, default=[])
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import cell, harness
    from bench.program import load_config

    cell.enable_cache()
    bm = harness.load_benchmark()
    runner = cell.runner_for(
        load_config(harness.find_cell(bm, args.workload)["config"]))
    out = []

    def emit(rec):
        print(json.dumps(rec), flush=True)
        out.append(rec)

    def one(seed, kind, plant=None):
        box = {}

        def grab(h):
            box["h"] = h
            if plant is not None:
                plant(h)

        t0 = time.perf_counter()
        stdout, sys.stdout = sys.stdout, io.StringIO()
        try:
            cell.run_cell(args.workload, seed, args.seconds, False,
                          t_process=time.perf_counter(), on_run=grab)
        finally:
            sys.stdout = stdout
        h = box["h"]
        return h, record(seed, kind, h.outcome, time.perf_counter() - t0)

    for seed in sorted(set(args.seeds) | set(args.control_seeds)
                       | set(args.fault_seeds)):
        h, rec = one(seed, "program")
        if seed in args.seeds or seed in args.control_seeds:
            emit(rec)
        if seed in args.control_seeds:
            t0 = time.perf_counter()
            emit(record(seed, "control", runner.control(h),
                        time.perf_counter() - t0))
        if seed in args.fault_seeds:
            for name, plant in runner.FAULTS.items():
                emit(one(seed, f"fault:{name}", plant)[1])
    if args.out:
        Path(args.out).write_text(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
