"""A short profiler trace of the window, and its reduction to device busy
time, idle share, exposed collective time, kernel time and idle gaps.

The trace is read with ``jax.profiler.ProfileData`` (nothing else). A
device is a plane named ``/device:TPU:<n>``; its operations are the events
of its ``XLA Ops`` line. The harness's own spans are ``bench.<name>``
annotations on the host planes, on the same clock, and bound the traced
window (``bench.traced``)."""
from __future__ import annotations

import glob
import re
import shutil
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional, Tuple

Interval = Tuple[int, int]

COLLECTIVE = re.compile(
    r"(all-to-all|all-reduce|all-gather|reduce-scatter|collective-permute"
    r"|send|recv|collective-broadcast)")

OUT_DIR = Path(__file__).resolve().parents[1] / ".bench_out"


@dataclass
class Op:
    start: int
    end: int
    name: str                # the HLO instruction's name, e.g. fusion.12
    text: str = ""           # the instruction as the trace gives it


INSTR = re.compile(r"^%([\w.-]+) = ")
SHAPE = re.compile(r"\b(pred|s8|u8|s16|u16|s32|u32|s64|u64|bf16|f16|f32|f64)"
                   r"\[([\d,]*)\](\{[^{}]*\})?")
def instruction(text: str) -> str:
    """The name of the HLO instruction an ``XLA Ops`` event carries."""
    m = INSTR.match(text)
    return m.group(1) if m else text


def shapes(text: str):
    """(result shapes, operand shapes) of an instruction's text, each a
    list of (dtype, dims, in_hbm). A layout that ends in ``S(1)`` puts
    the array in the core's on-chip memory (VMEM) rather than HBM."""
    def parse(part):
        return [(d, tuple(int(x) for x in dims.split(",") if x),
                 "S(1)" not in layout)
                for d, dims, layout in SHAPE.findall(part)]
    head, _, rest = text.partition("custom-call(")
    depth, end = 1, len(rest)
    for i, ch in enumerate(rest):
        depth += {"(": 1, ")": -1}.get(ch, 0)
        if depth == 0:
            end = i
            break
    return parse(head), parse(rest[:end])


def union(iv: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(iv: List[Interval]) -> int:
    return sum(b - a for a, b in union(iv))


def clip(iv: List[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def minus(a: List[Interval], b: List[Interval]) -> int:
    """Length of union(a) not covered by union(b)."""
    ua, ub = union(a), union(b)
    out, j = 0, 0
    for s, e in ua:
        cur = s
        while j < len(ub) and ub[j][1] <= cur:
            j += 1
        k = j
        while k < len(ub) and ub[k][0] < e:
            if ub[k][0] > cur:
                out += ub[k][0] - cur
            cur = max(cur, ub[k][1])
            k += 1
        if cur < e:
            out += e - cur
    return out


def gaps(busy: List[Interval], lo: int, hi: int) -> List[Interval]:
    out, cur = [], lo
    for a, b in union(clip(busy, lo, hi)):
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if cur < hi:
        out.append((cur, hi))
    return out


def is_collective(op: "Op") -> bool:
    """A collective, or a fusion that calls one."""
    if COLLECTIVE.match(op.name):
        return True
    m = re.search(r"calls=%([\w.-]+)", op.text)
    return bool(m and COLLECTIVE.match(m.group(1)))


CONTAINER = re.compile(r"^(while|conditional|call)(\.|$)")


def base_name(name: str) -> str:
    """An operation's name without its numeric suffix (``fusion.12`` ->
    ``fusion``)."""
    return re.sub(r"(\.\d+)+$", "", name)


@dataclass
class Reduced:
    """A traced window, reduced."""
    t0: int
    t1: int
    ops: Dict[str, List[Op]]                 # device -> operations
    host: List[Tuple[int, int, str]]         # bench spans (ns, ns, name)

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def busy_ns(self, dev: str) -> int:
        return total(clip([(o.start, o.end) for o in self.ops[dev]],
                          self.t0, self.t1))

    @property
    def busy_s(self) -> float:
        """Device busy seconds, averaged over the chips traced."""
        if not self.ops:
            return 0.0
        return sum(self.busy_ns(d) for d in self.ops) / len(self.ops) * 1e-9

    def idle_share(self, dev: str) -> float:
        return 1.0 - self.busy_ns(dev) / (self.t1 - self.t0)

    def exposed_collective_ns(self, dev: str) -> int:
        """Time in collective operations during which no other operation
        runs on the device."""
        ops = self.ops[dev]
        coll = clip([(o.start, o.end) for o in ops if is_collective(o)],
                    self.t0, self.t1)
        comp = clip([(o.start, o.end) for o in ops
                     if not is_collective(o)], self.t0, self.t1)
        return minus(coll, comp)

    def kernel_ops(self, dev: str, pattern: str) -> List[Op]:
        """The operations inside the window whose instruction name matches
        ``pattern``."""
        rx = re.compile(pattern)
        return [o for o in self.ops[dev] if rx.match(o.name)
                and o.start >= self.t0 and o.end <= self.t1]

    def host_label(self, t: int) -> str:
        best = None
        for a, b, name in self.host:
            if a <= t <= b and name != "traced" and \
                    (best is None or b - a < best[1] - best[0]):
                best = (a, b, name)
        return best[2] if best else "outside bench spans"

    def breakdown(self, top: int = 10) -> Dict:
        ops: Dict[str, float] = {}
        idle: List[Tuple[str, float]] = []
        for dev, evs in self.ops.items():
            for o in evs:
                # a loop's event spans the operations of its body
                if o.start >= self.t0 and o.end <= self.t1 and \
                        not CONTAINER.match(o.name):
                    k = base_name(o.name)
                    ops[k] = ops.get(k, 0.0) + (o.end - o.start) * 1e-9
            for a, b in gaps([(o.start, o.end) for o in evs], self.t0,
                             self.t1):
                idle.append((f"{dev}: {self.host_label((a + b) // 2)}",
                             (b - a) * 1e-9))
        n = max(1, len(self.ops))
        dev_ops = sorted(((k, v / n) for k, v in ops.items()),
                         key=lambda kv: -kv[1])[:top]
        idle.sort(key=lambda kv: -kv[1])
        return {"device_ops": [list(x) for x in dev_ops],
                "idle_gaps": [list(x) for x in idle[:top]]}


def device_index(plane_name: str) -> Optional[int]:
    m = re.fullmatch(r"/device:TPU:(\d+)", plane_name)
    return int(m.group(1)) if m else None


def reduce_file(path: str) -> Reduced:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: Dict[str, List[Op]] = {}
    host: List[Tuple[int, int, str]] = []
    for plane in pd.planes:
        idx = device_index(plane.name)
        if idx is not None:
            evs: List[Op] = []
            names = [line.name for line in plane.lines]
            want = "XLA Ops" if "XLA Ops" in names else None
            for line in plane.lines:
                if line.name != want and not (want is None
                                              and "Ops" in line.name):
                    continue
                for e in line.events:
                    s = int(e.start_ns)
                    evs.append(Op(s, s + int(e.duration_ns),
                                  instruction(e.name), e.name))
            ops[f"TPU:{idx}"] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        s = int(e.start_ns)
                        host.append((s, s + int(e.duration_ns),
                                     e.name[len("bench."):]))
    win = [(a, b) for a, b, n in host if n == "traced"]
    if not win:
        raise ValueError("trace has no bench.traced span")
    t0, t1 = win[0]
    red = Reduced(t0, t1, ops, host)
    # the device's clock should be the host's; where the traced window
    # holds almost none of the device's operations, it is not, and the
    # window is taken from the operations themselves
    every = [o for evs in ops.values() for o in evs]
    inside = sum(o.end - o.start for o in every if o.start >= t0
                 and o.end <= t1)
    if every and inside < 0.1 * sum(o.end - o.start for o in every):
        red.t0 = min(o.start for o in every)
        red.t1 = max(o.end for o in every)
    return red


class WindowTracer:
    """Traces the last ``trace_s`` seconds of the window, between steps
    of the runner's loop, and reduces the trace when it stops."""

    def __init__(self, h, rec, trace_s: float = 4.0):
        self.h, self.rec = h, rec
        self.trace_s = min(trace_s, h.seconds)
        self.dir = OUT_DIR / f"trace-{h.cell['name']}"
        self.running = False
        self.done = False
        self.reduced: Optional[Reduced] = None
        self._ann = None

    def start(self, now: float):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self.rec.annotate = True
        self._ann = jax.profiler.TraceAnnotation("bench.traced")
        self._ann.__enter__()
        self.running = True

    def stop(self, now: float):
        import jax
        self._ann.__exit__(None, None, None)
        self.rec.annotate = False
        jax.profiler.stop_trace()
        self.running, self.done = False, True
        files = glob.glob(str(self.dir / "**" / "*.xplane.pb"),
                          recursive=True)
        self.reduced = reduce_file(files[0])
        shutil.rmtree(self.dir, ignore_errors=True)

    def poll(self, now: float, w0: float, w1: float):
        if not self.running and not self.done and now >= w1 - self.trace_s:
            self.start(now)
        elif self.running and now >= w1:
            self.stop(now)

    def stop_if_running(self):
        import time
        if self.running:
            self.stop(time.perf_counter())
