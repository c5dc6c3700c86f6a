"""Cut-down copies of the benchmark's configurations and mixes, small
enough for the CPU: the same files with every size shrunk, so a test
drives the harness end to end without a chip. The sizes of an
architecture are its overlay, ``tiny_<reference>.py`` beside this file,
found by the configuration's ``"reference"`` name."""
from __future__ import annotations

import copy
import importlib
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

TINY_SERVING = {"max_seq": 128, "slots": 4, "page_size": 8, "n_pages": 64,
                "chunk": 16, "admit_k": 0}


def conf(name: str, **extra) -> dict:
    """The benchmark's configuration ``name``, cut down."""
    return shrink(json.loads(
        (ROOT / "bench" / "configs" / f"{name}.json").read_text()), **extra)


def shrink(c: dict, **extra) -> dict:
    """A configuration cut down by its architecture's overlay (its
    ``apply(c)`` shrinks the model and sets the limits for that size)."""
    c = copy.deepcopy(c)
    importlib.import_module(f"tiny_{c['reference']}").apply(c)
    if "serving" in c:
        c["serving"] = dict(TINY_SERVING)
    c.update(extra)
    return c


def chat_mix() -> dict:
    return {"kind": "serve_open_loop",
            "arrival": {"process": "poisson", "rate_rps": 8.0},
            "prompt_tokens": {"dist": "lognormal", "median": 20,
                              "sigma": 0.8, "min": 4, "max": 90},
            "output_tokens": {"dist": "lognormal", "median": 6,
                              "sigma": 0.7, "min": 2, "max": 24},
            "steady_start": {"token_period_s": 0.1},
            "warmup_s": 0.5, "drain_s": 30}
