"""One run of one cell: set-up, the measured window, the comparison that
decides ``correct``, and the result line."""
from __future__ import annotations

import importlib
import time
from typing import Dict, Optional

from bench import harness, traffic
from bench.harness import NoDevice  # noqa: F401  (run.py catches it)
from bench.peaks import Peak, peak_for
from bench.program import load_config, reference_of


class Run:
    """The context a cell's runner works in."""

    def __init__(self, bm, cell, conf, mix, seed, seconds, trace, devs,
                 peak: Peak, t_process: float):
        self.bm, self.cell, self.conf, self.mix = bm, cell, conf, mix
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.devs, self.peak, self.chips = devs, peak, cell["chips"]
        self.ref = reference_of(conf)
        self.spec = self.ref.spec(conf)
        self.t_process = t_process
        self.setup_s: Optional[float] = None
        self.memory_peak: Optional[int] = None
        # fault planting for the tests and the calibration runs: called
        # with the engine once it is built
        self.on_engine = None
        self.compiles = CompileCounter()

    def mark_window_start(self):
        self.setup_s = time.perf_counter() - self.t_process
        self.compiles.start()

    def mark_window_end(self):
        self.compiles.stop()

    def read_memory(self):
        self.memory_peak = harness.memory_peak_bytes(self.devs)

    def tracer(self, rec):
        from bench import trace
        return trace.WindowTracer(self, rec)


class CompileCounter:
    """Compile requests (persistent-cache hits and misses alike) between
    ``start`` and ``stop``: the window should hold none."""

    _live = []

    def __init__(self):
        self.n = 0
        self.on = False
        if not CompileCounter._live:
            import jax
            jax.monitoring.register_event_listener(CompileCounter._event)
        CompileCounter._live[:] = [self]

    @staticmethod
    def _event(name, **kw):
        for c in CompileCounter._live:
            if c.on and name == \
                    "/jax/compilation_cache/compile_requests_use_cache":
                c.n += 1

    def start(self):
        self.on = True

    def stop(self):
        self.on = False


def runner_for(conf: Dict):
    """The module that drives a configuration's entry point, found by
    name: ``bench/<entry>.py`` (``run``, ``control``, ``FAULTS``)."""
    return importlib.import_module(f"bench.{conf['entry']}")


def enable_cache():
    """The program's persistent compilation cache (``<checkout>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` names one), holding every
    program, however quick to compile."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_process: float, bm: Optional[Dict] = None,
             conf: Optional[Dict] = None, mix: Optional[Dict] = None,
             peak: Optional[Peak] = None, on_run=None,
             cache: bool = True) -> int:
    """Run the cell and print its result line. The keyword overrides are
    for the CPU tests, which skip the device check (``peak`` given) and
    run a cut-down configuration."""
    import jax

    if cache:
        enable_cache()
    bm = bm or harness.load_benchmark()
    cell = harness.find_cell(bm, workload)
    if peak is None:
        devs = harness.devices(cell["chips"])
        peak = peak_for(devs[0].device_kind)
    else:
        devs = jax.devices()[:cell["chips"]]
    conf = conf or load_config(cell["config"])
    mix = mix or traffic.load(cell["traffic"])
    h = Run(bm, cell, conf, mix, seed, seconds, trace, devs, peak, t_process)
    if on_run is not None:
        on_run(h)
    runner = runner_for(conf)
    out = runner.run(h)
    out.notes.append(f"compiles in the window: {h.compiles.n}")
    out.device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs), "memory_peak_bytes": h.memory_peak}
    if trace:
        tr = out.run.get("trace")
        if tr is not None:
            out.device["busy_s"] = tr.busy_s
            out.device["window_s"] = tr.window_s
            out.breakdown = tr.breakdown()
        metrics = {}
        for m in harness.reported_per_layer(bm, workload):
            v = harness.load_reader(m["name"]).read(out.run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        vals = dict(out.end_to_end, setup_s=h.setup_s)
        metrics = {m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
                   for m in harness.reported_end_to_end(bm, workload)}
    harness.print_result(out, metrics)
    h.outcome = out
    return 0
