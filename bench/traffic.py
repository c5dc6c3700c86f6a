"""The one traffic generator: every mix is a data file under
``bench/traffic/`` that this module reads.

A serving mix is an open loop: request ``i`` is due at ``due[i]`` seconds
after the load starts, whether or not the engine has caught up. The load
starts from the slots at their steady occupancy (``inflight``: the
requests that would still be decoding had the load been running for
long), then runs in three segments, warm-up, window and drain. Each
segment's inter-arrival gaps and request sizes are a fixed multiset
(quantiles of the mix's distributions) in one fixed, shuffled order: one
trace of arrivals and lengths, the same for every seed. The seed draws
the prompts' token ids (and the weights). Below the knee as well as
above it, the order alone moves the window's tail by whole admission
chunks, far more than two runs of one order differ (see PERF.md), so the
order is not the seed's to change.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist
from typing import Dict, List

import numpy as np

DIR = Path(__file__).resolve().parent / "traffic"


def load(name: str) -> Dict:
    path = DIR / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"traffic mix {name!r}: no file {path}")
    return json.loads(path.read_text())


def rng_for(seed: int, purpose: str) -> np.random.Generator:
    """A generator for one purpose of one seed; any whole number works as
    a seed, far beyond 32 bits."""
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) % 2**128,
                                int.from_bytes(purpose.encode(), "little")]))


def length_quantiles(dist: Dict, n: int) -> np.ndarray:
    """``n`` lengths at the probabilities (i + 1/2)/n of a lognormal given
    by its median and sigma, rounded and clipped to [min, max]."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.round(dist["median"] * np.exp(dist["sigma"] * z))
    return np.clip(x, dist["min"], dist["max"]).astype(np.int64)


def gap_quantiles(rate: float, n: int) -> np.ndarray:
    """``n`` exponential inter-arrival gaps of mean 1/rate at the
    probabilities (i + 1/2)/n."""
    p = (np.arange(n) + 0.5) / n
    return -np.log1p(-p) / rate


@dataclass(frozen=True)
class Request:
    due: float             # seconds after the load starts
    prompt_len: int
    max_new: int
    in_window: bool


def inflight(mix: Dict, rate: float) -> List[Request]:
    """The requests in flight when the load has run for long, at ``rate``
    requests/s and the mix's steady token period (seconds between two
    tokens of a decoding request): past arrivals at evenly spaced ages,
    paired with the mix's output quantiles in one fixed order, of which
    those still decoding come back as requests due at the start, their
    prompt extended by the tokens already generated and ``max_new`` the
    tokens still owed. The same set for every seed."""
    steady = mix.get("steady_start")
    if not steady:
        return []
    tau = float(steady["token_period_s"])
    out = mix["output_tokens"]
    n = max(1, int(round(rate * out["max"] * tau)))
    ages = (np.arange(n) + 0.5) / rate
    order = rng_for(0, "inflight")
    plen = order.permutation(length_quantiles(mix["prompt_tokens"], n))
    olen = order.permutation(length_quantiles(out, n))
    reqs = []
    for age, p, o in zip(ages, plen, olen):
        done = int(age / tau)
        if done < o:
            reqs.append(Request(0.0, int(p) + done, int(o) - done, False))
    return reqs


@dataclass(frozen=True)
class Schedule:
    requests: List[Request]
    window_start: float    # seconds after the load starts
    window_end: float
    seed: int

    def prompt(self, i: int, vocab: int) -> List[int]:
        """Token ids of request ``i``'s prompt, drawn from the seed (ids
        1..vocab-1; no shared prefixes)."""
        r = rng_for(self.seed, f"prompt{i}")
        return r.integers(1, vocab, size=self.requests[i].prompt_len
                          ).tolist()


def open_loop(mix: Dict, seed: int, seconds: float) -> Schedule:
    """The arrival schedule of a serving mix for a window of ``seconds``."""
    if mix["arrival"]["process"] != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrival']}")
    rate = float(mix["arrival"]["rate_rps"])
    segs = (("warmup", float(mix["warmup_s"])), ("window", float(seconds)),
            ("drain", float(mix["drain_s"])))
    reqs: List[Request] = inflight(mix, rate)
    t = 0.0
    window = (segs[0][1], segs[0][1] + segs[1][1])
    for name, length in segs:
        if length <= 0:
            continue
        shuffle = rng_for(0, f"order-{name}")
        n = max(1, int(round(rate * length)))
        gaps = gap_quantiles(rate, n)
        gaps *= length / gaps.sum()          # the segment lasts ``length``
        gaps = shuffle.permutation(gaps)
        plen = shuffle.permutation(length_quantiles(mix["prompt_tokens"], n))
        olen = shuffle.permutation(length_quantiles(mix["output_tokens"], n))
        start = t
        for g, p, o in zip(gaps, plen, olen):
            t += float(g)
            reqs.append(Request(t, int(p), int(o), name == "window"))
        t = start + length
    # the window segment's arrivals lie in (window start, window end]
    return Schedule(reqs, window[0], window[1], seed)



def expected_window_requests(mix: Dict, seconds: float) -> int:
    return max(1, int(round(float(mix["arrival"]["rate_rps"]) * seconds)))
