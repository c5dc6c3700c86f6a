"""Training launcher CLI.

Single host (CPU/debug):
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b-smoke \
      --steps 50 --batch 4 --seq 64

Multi-host TPU fleet: run the same command per host under your cluster
runner; jax.distributed.initialize() picks coordinator/host ids from the TPU
environment. --mesh data,model sizes must multiply to the global device
count. Checkpoints are restart-safe (see training/trainer.py).
"""
import argparse
import dataclasses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mesh", default="", help="e.g. 16,16 (data,model); "
                    "empty = single device, no mesh")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--impl", default="",
                    help="MoE transport override: naive|coarse|comet")
    ap.add_argument("--plan-cache", default="",
                    help="tuned adaptive-transport plan cache (JSON); the "
                         "train step resolves fwd+bwd MoE schedules from it")
    ap.add_argument("--plan-hw", default="",
                    help="hardware key for plan lookup (default tpu_v5e)")
    ap.add_argument("--sp-residual", action="store_true")
    ap.add_argument("--distributed", action="store_true",
                    help="call jax.distributed.initialize() (TPU fleet)")
    args = ap.parse_args()

    import jax

    from repro.launch.compile_cache import enable_compile_cache
    if args.distributed:
        jax.distributed.initialize()
    enable_compile_cache()

    from repro.configs.base import ShapeConfig, get_config
    from repro.parallel.mesh import make_mesh
    from repro.training.trainer import Trainer, TrainerConfig

    cfg = get_config(args.arch)
    if args.impl and cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, impl=args.impl))
    if args.sp_residual:
        cfg = dataclasses.replace(cfg, sp_residual=True)

    mesh = None
    if args.mesh:
        sizes = tuple(int(x) for x in args.mesh.split(","))
        axes = ("data", "model")[:len(sizes)] if len(sizes) <= 2 else \
            ("pod", "data", "model")
        mesh = make_mesh(sizes, axes)

    shape = ShapeConfig("train", seq_len=args.seq, global_batch=args.batch,
                        kind="train")
    tcfg = TrainerConfig(ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                         plan_cache=args.plan_cache, plan_hw=args.plan_hw)
    out = Trainer(cfg, shape, mesh, tcfg).run(args.steps)
    ls = [m["loss"] for m in out["metrics"]]
    print(f"final_step={out['final_step']} restarts={out['restarts']} "
          f"loss {ls[0]:.4f} -> {ls[-1]:.4f}" if ls else "no steps run")


if __name__ == "__main__":
    main()
