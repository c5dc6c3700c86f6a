"""Run one cell of BENCHMARK.json on the machine this is started on.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. The cell names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); with ``--trace 1`` each of the cell's
per-layer metrics is read by ``bench/metrics/<metric>.py`` from a short
profiler trace of the window. The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(with ``--trace 1`` also ``breakdown``), and last ``checks``, each number
compared with its limit. Exits 2 with no result where JAX finds no TPU or
fewer chips than the cell asks for, or outside a checkout of this repo.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if not (ROOT / "src" / "repro").is_dir() or \
            not (ROOT / "BENCHMARK.json").is_file():
        print("bench/run.py: run it from a checkout of this repo (needs "
              "src/repro and BENCHMARK.json)", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from bench import cell

    try:
        return cell.run_cell(args.workload, args.seed, args.seconds,
                             bool(args.trace), t_process=T_PROCESS)
    except cell.NoDevice as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())
