"""Serving launcher CLI: continuous-batching engine (chunked prefill +
slot-based decode) with tuned per-phase plans, or — with ``--disagg`` —
the router/worker topology (prefill workers + decode workers with
paged-page KV migration, serving/disagg.py).

Engine flags are grouped (engine / paging / robustness / chaos / disagg)
and map 1:1 onto :class:`repro.serving.EngineConfig`; the benchmarks
build engines through the same config, so the CLI and the gates can
never construct different engines from the same knobs.

  PYTHONPATH=src python -m repro.launch.serve --arch granite-moe-3b-a800m-smoke \
      --batch 4 --max-new 16 --plan-cache plans/tpu_v5e.json --plan-hw tpu_v5e

  # disaggregated: 1 prefill worker + 2 decode workers, paged handoff,
  # mixed-length Poisson trace + robustness summary
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b-smoke \
      --disagg --page-size 8 --prefill-workers 1 --decode-workers 2 \
      --requests 12 --chaos 0.02
"""
import argparse
import time
from collections import Counter

import numpy as np


def _make_trace(cfg, args, mixed: bool):
    """The request workload: fixed-length prompts for the classic mode,
    mixed lengths (0.5x–2x --prompt-len) for the disagg trace — the
    prefill-heavy mix is what the topology exists for."""
    rng = np.random.default_rng(args.seed)
    n_req = args.requests or args.batch
    lens = (rng.integers(max(1, args.prompt_len // 2),
                         2 * args.prompt_len + 1, size=n_req)
            if mixed else np.full(n_req, args.prompt_len))
    return [rng.integers(1, cfg.vocab_size, size=int(n)).tolist()
            for n in lens]


def _print_engine_summary(eng, prompts, args, dt):
    n_prefill = sum(len(p) for p in prompts)
    tput = (n_prefill + eng.decode_tokens) / dt
    print(f"{n_prefill} prefill toks + {eng.decode_steps} decode "
          f"steps ({eng.decode_tokens} toks) across {args.batch} slots / "
          f"{len(prompts)} requests in {dt:.2f}s  ({tput:.0f} tok/s)")
    print(f"phase timings: prefill {eng.prefill_s:.2f}s "
          f"({eng.prefill_tokens / max(eng.prefill_s, 1e-9):.0f} tok/s), "
          f"decode {eng.decode_s:.2f}s "
          f"({eng.decode_s / max(eng.decode_steps, 1) * 1e3:.1f} ms/step)")
    if eng.paged:
        print(f"paged cache: page {eng.page_size} toks, "
              f"{eng.n_pages - 1} usable pages "
              f"({eng.free_pages} free after drain), "
              f"{eng.admissions} admissions")
    if eng.faults is not None or eng.failures or eng.expired or \
            eng.quarantined or eng.shed:
        statuses = Counter(r.status.value for r in eng.finished.values())
        print(f"robustness: statuses {dict(statuses)}, "
              f"{eng.failures} step failures / {eng.recoveries} recoveries, "
              f"{eng.quarantined} quarantined, {eng.expired} expired, "
              f"{eng.shed} shed, "
              f"{len(eng.monitor.flagged)} straggler steps")
        if eng.faults is not None:
            print(f"injected: {eng.faults.counts}")


def _print_router_summary(router, prompts, dt):
    s = router.summary()
    ec = router.econfig
    total = s["prefill_tokens"] + s["decode_tokens"]
    print(f"disagg: {ec.prefill_workers} prefill x "
          f"{ec.prefill_slots or ec.batch_size} slots -> "
          f"{ec.decode_workers} decode x "
          f"{ec.decode_slots or ec.batch_size} slots, "
          f"page {router.page_size} toks")
    print(f"{s['prefill_tokens']} prefill toks + {s['decode_tokens']} "
          f"decode toks / {len(prompts)} requests in {dt:.2f}s "
          f"({total / dt:.0f} tok/s)")
    print(f"migration: {s['migrations']} handoffs, {s['pages_moved']} "
          f"pages moved, {s['remigrations']} re-migrations, "
          f"{s['duplicate_handoffs']} duplicates dropped")
    ttfts = [r.ttft_s for r in router.finished.values()
             if r.first_token_t > 0]
    if ttfts:
        print(f"ttft: mean {np.mean(ttfts) * 1e3:.1f} ms, "
              f"p99 {np.percentile(ttfts, 99) * 1e3:.1f} ms")
    statuses = Counter(r.status.value for r in router.finished.values())
    print(f"robustness: statuses {dict(statuses)}, "
          f"{s['failures']} worker failures / {s['recoveries']} "
          f"recoveries, {s['quarantined']} quarantined, "
          f"{s['expired']} expired, {s['shed']} shed")
    for name, w in s["per_worker"].items():
        print(f"  {name}: {w}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    wl = ap.add_argument_group("workload")
    wl.add_argument("--requests", type=int, default=0,
                    help="total requests to serve (default: --batch)")
    wl.add_argument("--max-new", type=int, default=16)
    wl.add_argument("--prompt-len", type=int, default=12,
                    help="prompt tokens (disagg: mean of a 0.5x-2x mix)")
    from repro.serving import EngineConfig
    EngineConfig.add_cli_args(ap)
    args = ap.parse_args()

    from repro.configs.base import get_config
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    cfg = get_config(args.arch)
    ec = EngineConfig.from_cli_args(
        args, chaos_horizon=4 * (args.max_new + args.prompt_len))
    if args.chaos > 0:
        inj = ec.make_faults()
        print(f"chaos: {inj.plan.summary()} over {ec.chaos_horizon} steps "
              f"(seed {args.chaos_seed})")
    eng = ec.build(cfg)
    prompts = _make_trace(cfg, args, mixed=ec.disagg)

    t0 = time.perf_counter()
    rids = [eng.submit(p, max_new=args.max_new) for p in prompts]
    eng.run()
    dt = time.perf_counter() - t0
    for i, rid in enumerate(rids):
        r = eng.finished[rid]
        tag = "" if r.status.value == "ok" else f"  [{r.status.value}]"
        print(f"req{i} (len {len(prompts[i])}): {r.tokens}{tag}")
    if ec.disagg:
        _print_router_summary(eng, prompts, dt)
    else:
        _print_engine_summary(eng, prompts, args, dt)


if __name__ == "__main__":
    main()
