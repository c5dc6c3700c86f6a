"""The seam between the harness and the system under test: the program's
model configuration built from a configuration file, and the program's
parameter tree filled with the weights the reference makes from the seed
(one jitted call, on the device, in the dtype they are served in).

What is particular to an architecture is found by the configuration's
``"reference"`` name: its plain reference (``bench/reference/<name>.py``)
and its adapter to the program (``bench/adapters/<name>.py``)."""
from __future__ import annotations

import dataclasses
import importlib
import json
from pathlib import Path
from typing import Dict

import jax
import jax.numpy as jnp

CONFIG_DIR = Path(__file__).resolve().parent / "configs"


def load_config(name: str) -> Dict:
    path = CONFIG_DIR / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"configuration {name!r}: no file {path}")
    return json.loads(path.read_text())


def reference_of(conf: Dict):
    """The configuration's plain reference module."""
    return importlib.import_module(f"bench.reference.{conf['reference']}")


def adapter_of(conf: Dict):
    """The configuration's adapter module to the program."""
    return importlib.import_module(f"bench.adapters.{conf['reference']}")


def program_config(conf: Dict):
    """The program's ``ModelConfig``: the registered arch with the file's
    changes, and the MoE schedule pinned (no plan file, no cost model)."""
    from repro.configs.base import get_config

    p = conf["program"]
    cfg = get_config(p["arch"])
    changes = dict(p.get("changes", {}))
    if "attn" in changes:
        changes["attn"] = dataclasses.replace(cfg.attn, **changes["attn"])
    cfg = dataclasses.replace(cfg, **changes)
    moe = dataclasses.replace(cfg.moe, plan_override=True, plan_cache="",
                              **p.get("moe_knobs", {}))
    cfg = dataclasses.replace(cfg, moe=moe)
    bad = adapter_of(conf).check(cfg, reference_of(conf).spec(conf))
    if bad:
        raise ValueError(f"{conf['name']}: the program's config departs from"
                         f" the file (program, file): {bad}")
    return cfg


def make_program_params(conf: Dict, seed: int, ctx):
    """The program's parameters, made on the device in one jitted call
    from the seed; checked against the program's own abstract tree."""
    from repro.models import lm

    ref, adapter = reference_of(conf), adapter_of(conf)
    m = ref.spec(conf)
    init = conf["init"]
    W = ctx.model_size if ctx.active else 1
    keys = jnp.stack([ref.layer_key(seed, l) for l in range(m["n_layers"])])
    tkey = ref.top_key(seed)

    def build(keys, tkey):
        return adapter.build(m, init, keys, tkey, W)

    cfg = program_config(conf)
    want = lm.abstract_params(cfg, ctx)
    got = jax.eval_shape(build, keys, tkey)
    sig = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda x: (x.shape, jnp.dtype(x.dtype).name), t)
    if jax.tree_util.tree_structure(want) != \
            jax.tree_util.tree_structure(got) or sig(want) != sig(got):
        raise ValueError(f"the program's parameter tree changed layout; "
                         f"bench/adapters/{conf['reference']}.py must "
                         f"follow it")
    return jax.jit(build)(keys, tkey)
