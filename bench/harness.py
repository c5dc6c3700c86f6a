"""What every cell shares: reading ``BENCHMARK.json`` and the files a cell
names, the device check, the per-layer metric readers, and the result
line."""
from __future__ import annotations

import importlib.util
import json
import math
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
METRICS_DIR = Path(__file__).resolve().parent / "metrics"


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_benchmark(root: Path = ROOT) -> Dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(bm: Dict, name: str) -> Dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bm['workloads']]}")


def reported_end_to_end(bm: Dict, cell: str) -> List[Dict]:
    return [m for m in bm["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]]


def reported_per_layer(bm: Dict, cell: str) -> List[Dict]:
    e2e = {m["name"] for m in reported_end_to_end(bm, cell)}
    return [m for m in bm["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


def load_reader(name: str):
    """``bench/metrics/<name>.py``: a module with ``read(run) -> float or
    None`` (None when the run has nothing to read for it)."""
    path = METRICS_DIR / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"per-layer metric {name!r}: no reader "
                                f"{path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def devices(chips: int):
    """The accelerator's devices; never the CPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"needs a TPU, JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found "
                       f"{len(devs)}")
    return devs[:chips]


def memory_peak_bytes(devs) -> Optional[int]:
    """Peak bytes in use on the fullest chip, where the backend says."""
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


@dataclass
class Check:
    """One number compared by ``correct``, with its limit (pass: value <=
    limit)."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclass
class Outcome:
    """What a cell's runner (``bench/<entry>.py``) hands back to
    ``run.py``."""
    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    checks: List[Check]
    run: Dict = field(default_factory=dict)      # what metric readers read
    device: Dict = field(default_factory=dict)
    breakdown: Optional[Dict] = None
    ok: bool = True                              # nothing else went wrong
    notes: List[str] = field(default_factory=list)

    @property
    def correct(self) -> bool:
        return self.ok and all(c.ok for c in self.checks)


def result_line(out: Outcome, metrics: Dict[str, Dict]) -> Dict:
    line = {"correct": out.correct, "attempted": out.attempted,
            "failed": out.failed, "metrics": metrics, "device": out.device}
    if out.breakdown is not None:
        line["breakdown"] = out.breakdown
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in out.checks}
    return line


def print_result(out: Outcome, metrics: Dict[str, Dict],
                 stream_out=None, stream_err=None) -> None:
    stream_out = stream_out or sys.stdout
    stream_err = stream_err or sys.stderr
    for n in out.notes:
        print(n, file=stream_err)
    print(f"correct = {out.correct}", file=stream_err)
    for c in out.checks:
        print(f"check {c.name} = {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAIL'}", file=stream_err, flush=True)
    print(json.dumps(result_line(out, metrics)), file=stream_out,
          flush=True)

