"""The benchmark's traffic generator: a pure function of the seed, every
seed offers the same trace of arrivals and lengths, and the load starts
from the slots at their steady occupancy."""
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import traffic  # noqa: E402

MIX = traffic.load("chat")
BIG = 2**31 + 12345          # beyond 32 signed bits


def test_schedule_is_a_pure_function_of_the_seed():
    a = traffic.open_loop(MIX, BIG, 40)
    b = traffic.open_loop(MIX, BIG, 40)
    assert a.requests == b.requests
    assert a.prompt(3, 49155) == b.prompt(3, 49155)
    # another seed: the same trace of arrivals and lengths, other tokens
    c = traffic.open_loop(MIX, BIG + 1, 40)
    assert a.requests == c.requests
    assert a.prompt(3, 49155) != c.prompt(3, 49155)


def test_every_seed_offers_the_same_window_work_in_the_same_order():
    """The same work in each segment, in the same shuffled order, every
    seed."""
    runs = [traffic.open_loop(MIX, s, 40) for s in (1, 2, BIG)]
    for field in ("prompt_len", "max_new"):
        orders = [tuple(getattr(r, field) for r in s.requests
                        if r.in_window) for s in runs]
        assert orders[0] == orders[1] == orders[2]
        assert list(orders[0]) != sorted(orders[0])
    def gaps(s):
        due = [s.window_start] + [r.due for r in s.requests if r.in_window]
        return sorted(round(b - a, 9) for a, b in zip(due, due[1:]))

    assert gaps(runs[0]) == gaps(runs[1]) == gaps(runs[2])
    counts = {sum(r.in_window for r in s.requests) for s in runs}
    assert counts == {traffic.expected_window_requests(MIX, 40)}


def test_window_arrivals_lie_in_the_window():
    s = traffic.open_loop(MIX, 7, 40)
    due = [r.due for r in s.requests if r.in_window]
    assert min(due) > s.window_start and max(due) <= s.window_end + 1e-9
    assert s.window_end - s.window_start == 40
    dues = [r.due for r in s.requests]
    assert dues == sorted(dues)


def test_lengths_follow_the_mix_and_fit_the_cache():
    s = traffic.open_loop(MIX, 11, 40)
    # the arrivals; the in-flight requests at the start carry the tokens
    # they have generated in their context
    arr = [r for r in s.requests if r.due > 0]
    p = np.array([r.prompt_len for r in arr])
    o = np.array([r.max_new for r in arr])
    assert p.min() >= 32 and p.max() <= 3584
    assert o.min() >= 16 and o.max() <= 512
    assert max(r.prompt_len + r.max_new for r in s.requests) <= 4096
    assert abs(np.median(p) - 600) < 60 and abs(np.median(o) - 150) < 20


def test_the_load_starts_at_steady_occupancy():
    """The in-flight requests due at the start: as many as Little's law
    gives (rate x mean output x token period), each owing part of its
    output, with its context extended by the tokens already generated;
    the same set for every seed."""
    rate = MIX["arrival"]["rate_rps"]
    tau = MIX["steady_start"]["token_period_s"]
    fl = traffic.inflight(MIX, rate)
    out = traffic.length_quantiles(MIX["output_tokens"], 1000)
    want = rate * out.mean() * tau
    assert abs(len(fl) - want) <= 0.15 * want + 1
    assert all(r.due == 0.0 and not r.in_window for r in fl)
    assert all(1 <= r.max_new <= 512 for r in fl)
    assert max(r.prompt_len + r.max_new for r in fl) <= 4096
    s = traffic.open_loop(MIX, BIG, 40)
    assert s.requests[:len(fl)] == fl
    assert traffic.open_loop(MIX, 3, 40).requests[:len(fl)] == fl


def test_no_steady_start_without_its_token_period():
    mix = {k: v for k, v in MIX.items() if k != "steady_start"}
    assert traffic.inflight(mix, 0.2) == []
