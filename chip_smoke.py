"""Chip smoke run: the main path of this repo, end to end, on a TPU.

    python chip_smoke.py             # one chip: serve, kernels, train
    python chip_smoke.py --chips 4   # four chips: the EP=4 comet ring only

One chip. ``granite-moe-3b-a800m`` is served at its registered full size
(32 layers, random weights from ``--seed``) through ``EngineConfig.build``
with a paged KV cache: 8 requests, prompts of 128-512 tokens, 32 new
tokens each. Then the Pallas kernels of the MoE path are checked against
their oracles at granite width, and ``Trainer`` takes 3 steps at granite
width with depth cut to 4 layers (full depth with AdamW state does not fit
16 GB), once with the ``xla`` and once with the ``pallas_fused`` expert
GEMM backend.

Four chips. One train step of the same 4-layer model on a (1, 4)
("data", "model") mesh with 10 experts per chip: the comet ring
(``pallas_fused``, streamed combine) against the naive all-to-all, from
the same initial state and batch.

Every check that fails is printed and the script exits 1. It exits 2
without a result when JAX finds no TPU, and when it is not inside a
checkout of this repo. The last line of a passing run is one JSON object
naming the device. Timings printed on the way are smoke information, not
benchmark results. Everything runs in this one process, which holds the
chip, and shares one persistent compilation cache
(``repro.launch.compile_cache``).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import re
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "granite-moe-3b-a800m"

# step-1 losses of the two expert GEMM backends: same weights and batch,
# bf16 compute, different accumulation order in the expert MLP
TRAIN_LOSS_RTOL = 5e-3
# comet ring vs naive all-to-all over 4 chips: same step, different
# transport and expert GEMM backend (pallas_fused vs xla)
EP_LOSS_RTOL = 5e-3
EP_GRAD_NORM_RTOL = 2e-2
# kernel vs oracle, bf16 inputs: max |kernel - oracle| / max |oracle|
KERNEL_RTOL = 2e-2


class SmokeFailure(AssertionError):
    """A check of the smoke run failed."""


def _check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


def pinned(cfg, **moe_knobs):
    """``cfg`` with its MoE schedule fixed by the given knobs: no plan
    cache is read, so what compiles comes only from committed files."""
    moe = dataclasses.replace(cfg.moe, plan_override=True, plan_cache="",
                              **moe_knobs)
    return dataclasses.replace(cfg, moe=moe)


def _abstract(args):
    import jax
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
        if isinstance(x, jax.Array) else x, args)


class _Recorder:
    """Wraps a jitted step and keeps the abstract arguments of its first
    call, so the compiled program can be inspected after the run (the
    real arguments are donated)."""

    def __init__(self, jitted):
        self.jitted = jitted
        self.args = None

    def __call__(self, *args):
        if self.args is None:
            self.args = _abstract(args)
        return self.jitted(*args)

    def hlo(self) -> str:
        return self.jitted.lower(*self.args).compile().as_text()


def serve_phase(cfg, *, seed: int = 0, slots: int = 8, max_seq: int = 2048,
                page: int = 16, chunk: int = 128, n_requests: int = 8,
                prompt_lens=(128, 512), max_new: int = 32) -> dict:
    """Serve ``n_requests`` random prompts through ``EngineConfig.build``
    with recovery off, and check every request finished ``ok`` with
    in-vocab tokens. On a TPU the compiled prefill and decode programs
    must contain the Pallas kernels (``tpu_custom_call``)."""
    import jax
    import numpy as np

    from repro.serving import EngineConfig

    ec = EngineConfig(max_seq=max_seq, batch_size=slots, chunk=chunk,
                      seed=seed, page_size=page, max_restarts=0,
                      recover=False)
    eng = ec.build(cfg)
    eng.prefill["jit"] = prefill = _Recorder(eng.prefill["jit"])
    eng.decode["jit"] = decode = _Recorder(eng.decode["jit"])
    rng = np.random.default_rng(seed)
    lens = rng.integers(prompt_lens[0], prompt_lens[1] + 1, size=n_requests)
    prompts = [rng.integers(1, cfg.vocab_size, size=int(n)).tolist()
               for n in lens]
    t0 = time.perf_counter()
    rids = [eng.submit(p, max_new=max_new) for p in prompts]
    eng.run()
    wall = time.perf_counter() - t0
    reqs = [eng.finished[r] for r in rids]
    statuses = [r.status.value for r in reqs]
    out = {"statuses": statuses, "failures": eng.failures,
           "recoveries": eng.recoveries,
           "new_tokens": sum(len(r.tokens) for r in reqs),
           "prompt_tokens": int(lens.sum()), "wall_s": wall}
    _check(all(s == "ok" for s in statuses), f"serve statuses {statuses}")
    _check(eng.failures == 0 and eng.recoveries == 0,
           f"serve failures {eng.failures}, recoveries {eng.recoveries}")
    _check(all(len(r.tokens) == max_new for r in reqs),
           f"serve token counts {[len(r.tokens) for r in reqs]}")
    _check(all(0 <= t < cfg.vocab_size for r in reqs for t in r.tokens),
           "serve emitted a token id outside the vocab")
    if jax.default_backend() == "tpu":
        for name, rec in (("prefill", prefill), ("decode", decode)):
            _check("tpu_custom_call" in rec.hlo(),
                   f"compiled {name} program has no Pallas kernel")
        out["tpu_custom_call"] = True
    return out


def _rel_err(got, want) -> float:
    import jax.numpy as jnp
    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    return float(jnp.max(jnp.abs(got - want))
                 / jnp.maximum(jnp.max(jnp.abs(want)), 1e-30))


def kernel_phase(cfg, *, seed: int = 0, rows: int = 512) -> dict:
    """The MoE path's Pallas kernels at ``cfg``'s widths, each against its
    oracle in ``kernels/ref.py`` (or the VJP of the fused MLP oracle),
    with the tilings the transport passes."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    d, f = cfg.d_model, cfg.moe.d_expert
    E, k, act = cfg.moe.num_experts, cfg.moe.top_k, cfg.activation
    T = 2048
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    bf16 = jnp.bfloat16
    errs = {}
    comb = jax.random.normal(ks[0], (T, k, d), bf16)
    wts = jax.nn.softmax(jax.random.normal(ks[1], (T, k)), axis=-1)
    errs["topk_combine"] = _rel_err(ops.topk_combine(comb, wts),
                                    ref.topk_combine_ref(comb, wts))
    blk = d // 4
    errs["topk_combine_col_block"] = _rel_err(
        ops.topk_combine(comb[..., :blk], wts),
        ref.topk_combine_ref(comb[..., :blk], wts))

    x = jax.random.normal(ks[2], (E, rows, d), bf16)
    scale = d ** -0.5
    w = {"w_gate": (jax.random.normal(ks[3], (E, d, f)) * scale).astype(bf16),
         "w_up": (jax.random.normal(ks[4], (E, d, f)) * scale).astype(bf16),
         "w_down": (jax.random.normal(ks[5], (E, f, d)) * f ** -0.5
                    ).astype(bf16)}
    dy = jax.random.normal(ks[6], (E, rows, d), bf16)

    def oracle(x, wg, wu, wd):
        return ref.fused_mlp_ref(x, wg, wu, wd, act)

    f32 = lambda a: a.astype(jnp.float32)
    y, vjp = jax.vjp(oracle, f32(x), f32(w["w_gate"]), f32(w["w_up"]),
                     f32(w["w_down"]))
    dx, dwg, dwu, dwd = vjp(f32(dy))
    errs["fused_mlp"] = _rel_err(ops.fused_mlp(x, w, act), y)
    errs["fused_mlp_col_block"] = _rel_err(
        ops.fused_mlp(x, w, act, col_slice=(0, blk), order="n_major"),
        y[..., :blk])
    errs["fused_mlp_dgrad"] = _rel_err(ops.fused_mlp_dgrad(x, w, dy, act), dx)
    g_, u_, d_ = ops.fused_mlp_wgrad(x, w, dy, act)
    errs["fused_mlp_wgrad"] = max(_rel_err(g_, dwg), _rel_err(u_, dwu),
                                  _rel_err(d_, dwd))
    bad = {n: e for n, e in errs.items() if not e <= KERNEL_RTOL}
    _check(not bad, f"kernels off their oracles (rtol {KERNEL_RTOL}): {bad}")
    return errs


def train_phase(cfg, *, gemm_impl: str, ckpt_dir, seed: int = 0,
                seq: int = 2048, batch: int = 4, steps: int = 3) -> dict:
    """``Trainer.run`` for ``steps`` steps from a fresh checkpoint
    directory with restarts off: every loss finite, no skipped update."""
    import numpy as np

    from repro.configs.base import ShapeConfig
    from repro.training.trainer import Trainer, TrainerConfig

    ckpt_dir = Path(ckpt_dir)
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    cfg = pinned(cfg, gemm_impl=gemm_impl)
    shape = ShapeConfig("smoke_train", seq_len=seq, global_batch=batch,
                        kind="train")
    tcfg = TrainerConfig(ckpt_dir=str(ckpt_dir), ckpt_every=10**9,
                         log_every=10**9, seed=seed, max_restarts=0)
    t0 = time.perf_counter()
    out = Trainer(cfg, shape, None, tcfg).run(steps)
    wall = time.perf_counter() - t0
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    losses = [m["loss"] for m in out["metrics"]]
    _check(len(losses) == steps, f"{gemm_impl}: {len(losses)} steps ran")
    _check(all(np.isfinite(losses)), f"{gemm_impl}: losses {losses}")
    _check(out["restarts"] == 0 and out["nan_skips"] == 0,
           f"{gemm_impl}: restarts {out['restarts']}, "
           f"nan_skips {out['nan_skips']}")
    return {"losses": losses, "wall_s": wall}


def compare_losses(a: float, b: float, rtol: float, what: str) -> float:
    rel = abs(a - b) / max(abs(b), 1e-30)
    _check(rel <= rtol, f"{what}: {a} vs {b} (rel {rel:.2e} > {rtol})")
    return rel


def ep_phase(cfg, mesh, *, ckpt_root, seed: int = 0, seq: int = 2048,
             batch: int = 4) -> dict:
    """One train step on ``mesh`` with the comet ring and one with the
    naive all-to-all, from the same initial state and batch. Checks loss
    and grad-norm agreement, that the expert weights are spread over every
    device of the model axis (E/ep experts each), and that the compiled
    comet step moves its tokens with collective-permutes."""
    from repro.configs.base import ShapeConfig
    from repro.training.trainer import Trainer, TrainerConfig

    ep = mesh.shape["model"]
    shape = ShapeConfig("smoke_ep", seq_len=seq, global_batch=batch,
                        kind="train")
    impls = {"comet": dict(impl="comet", gemm_impl="pallas_fused",
                           fused_combine=True),
             "naive": dict(impl="naive", gemm_impl="xla")}
    out = {}
    for name, knobs in impls.items():
        ckpt_dir = Path(ckpt_root) / f"ep_{name}"
        shutil.rmtree(ckpt_dir, ignore_errors=True)
        tcfg = TrainerConfig(ckpt_dir=str(ckpt_dir), ckpt_every=10**9,
                             log_every=10**9, seed=seed, max_restarts=0)
        tr = Trainer(pinned(cfg, ep=ep, **knobs), shape, mesh, tcfg)
        tr.built["jit"] = step = _Recorder(tr.built["jit"])
        res = tr.run(1)
        shutil.rmtree(ckpt_dir, ignore_errors=True)
        _check(res["restarts"] == 0 and res["nan_skips"] == 0,
               f"ep {name}: restarts {res['restarts']}, "
               f"nan_skips {res['nan_skips']}")
        m = res["metrics"][0]
        out[name] = {"loss": m["loss"], "grad_norm": m["grad_norm"]}
        if name == "comet":
            hlo = step.hlo()
            out["comet_collective_permutes"] = len(re.findall(
                r"collective-permute(?:-start)?\(", hlo))
            _check(out["comet_collective_permutes"] > 0,
                   "compiled comet step has no collective-permute")
            out["expert_shards"] = _expert_placement(tr.init_state(), ep,
                                                     cfg.moe.num_experts)
    out["loss_rel"] = compare_losses(out["comet"]["loss"],
                                     out["naive"]["loss"], EP_LOSS_RTOL,
                                     "comet vs naive loss")
    out["grad_norm_rel"] = compare_losses(
        out["comet"]["grad_norm"], out["naive"]["grad_norm"],
        EP_GRAD_NORM_RTOL, "comet vs naive grad norm")
    return out


def _expert_placement(state, ep: int, n_experts: int) -> dict:
    """Each expert weight must live on ``ep`` distinct devices with
    ``n_experts / ep`` experts per shard."""
    import jax

    placed = {}
    flat = jax.tree_util.tree_flatten_with_path(state["params"])[0]
    for path, leaf in flat:
        name = jax.tree_util.keystr(path)
        if "experts" not in name:
            continue
        shards = leaf.addressable_shards
        devs = {s.device for s in shards}
        # (..., ep, E_loc, in, out): the shard dim is 4th from the end
        e_loc = {s.data.shape[-3] * s.data.shape[-4] for s in shards}
        _check(len(devs) == ep, f"{name}: on {len(devs)} devices, want {ep}")
        _check(e_loc == {n_experts // ep},
               f"{name}: {e_loc} experts per shard, want {n_experts // ep}")
        placed[name] = {"devices": len(devs), "experts_per_shard":
                        n_experts // ep}
    _check(bool(placed), "no expert weights found in the train state")
    return placed


def _device_or_exit(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform}",
              file=sys.stderr)
        sys.exit(2)
    if len(devs) < chips:
        print(f"chip_smoke: --chips {chips} needs {chips} devices, JAX found "
              f"{len(devs)}", file=sys.stderr)
        sys.exit(2)
    return devs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=str(ROOT / "chip_smoke_out"),
                    help="scratch directory (train checkpoints)")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke: run it from a checkout of this repo",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    devs = _device_or_exit(args.chips)
    kind = devs[0].device_kind
    print(f"device: {devs[0].platform} {kind} x{len(devs)}; compile cache "
          f"{cache}", flush=True)

    from repro.configs.base import get_config
    from repro.parallel.mesh import make_mesh

    cfg = pinned(get_config(ARCH))
    small = dataclasses.replace(cfg, n_layers=4)
    phases = {}
    try:
        if args.chips == 4:
            mesh = make_mesh((1, 4), ("data", "model"))
            phases["ep4"] = ep_phase(small, mesh, ckpt_root=args.out,
                                     seed=args.seed)
            print(f"ep4: {json.dumps(phases['ep4'])}", flush=True)
        else:
            s = phases["serve"] = serve_phase(cfg, seed=args.seed)
            print(f"serve: statuses {s['statuses']}, failures "
                  f"{s['failures']}, recoveries {s['recoveries']}, "
                  f"tpu_custom_call {s.get('tpu_custom_call')}", flush=True)
            print(f"smoke info, not a benchmark: served "
                  f"{s['prompt_tokens']} prompt + {s['new_tokens']} new "
                  f"tokens in {s['wall_s']:.1f} s wall, compiles included "
                  f"({s['new_tokens'] / s['wall_s']:.1f} new tok/s)",
                  flush=True)
            phases["kernels"] = kernel_phase(cfg, seed=args.seed)
            print(f"kernels (rel err vs oracle): "
                  f"{json.dumps(phases['kernels'])}", flush=True)
            train = {}
            for impl in ("xla", "pallas_fused"):
                train[impl] = train_phase(
                    small, gemm_impl=impl, seed=args.seed,
                    ckpt_dir=Path(args.out) / f"train_{impl}")
                print(f"train {impl}: losses {train[impl]['losses']} "
                      f"({train[impl]['wall_s']:.1f} s wall, smoke info)",
                      flush=True)
            rel = compare_losses(train["pallas_fused"]["losses"][0],
                                 train["xla"]["losses"][0], TRAIN_LOSS_RTOL,
                                 "step-1 loss pallas_fused vs xla")
            print(f"train step-1 loss rel diff {rel:.2e} "
                  f"(rtol {TRAIN_LOSS_RTOL})", flush=True)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": kind, "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
