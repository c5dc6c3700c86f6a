"""chip_smoke.py's phases on the CPU at the -smoke size, called directly
(not through main(), which refuses to run without a TPU): a broken smoke
script shows up here before any chip time is spent. Plus the location of
the persistent compilation cache every entry point shares."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from repro.configs.base import get_config  # noqa: E402

SMOKE = chip_smoke.pinned(get_config(chip_smoke.ARCH + "-smoke"))


def test_serve_phase_smoke():
    out = chip_smoke.serve_phase(SMOKE, slots=4, max_seq=64, page=8,
                                 chunk=16, n_requests=5, prompt_lens=(8, 24),
                                 max_new=4)
    assert out["statuses"] == ["ok"] * 5
    assert out["new_tokens"] == 20
    assert out["failures"] == 0 and out["recoveries"] == 0


def test_train_phase_smoke_backends_agree(tmp_path):
    runs = {impl: chip_smoke.train_phase(SMOKE, gemm_impl=impl,
                                         ckpt_dir=tmp_path / impl, seq=32,
                                         batch=2, steps=2)
            for impl in ("xla", "pallas_fused")}
    for r in runs.values():
        assert len(r["losses"]) == 2 and np.all(np.isfinite(r["losses"]))
    chip_smoke.compare_losses(runs["pallas_fused"]["losses"][0],
                              runs["xla"]["losses"][0],
                              chip_smoke.TRAIN_LOSS_RTOL, "step-1 loss")
    assert not (tmp_path / "xla").exists()       # checkpoints cleaned up


def test_smoke_failure_is_raised_on_disagreement():
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.compare_losses(1.0, 1.1, 1e-3, "loss")


def _run(code: str, env_extra: dict, cwd) -> subprocess.CompletedProcess:
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_extra)
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, timeout=600,
                          env=env, cwd=cwd)


def test_ep_phase_on_four_host_devices(tmp_path):
    """The --chips 4 phase at -smoke size on 4 forced host devices: comet
    and naive agree, experts spread 2 per device, ring is permutes."""
    r = _run(f"""
        import sys
        sys.path.insert(0, {str(ROOT)!r})
        import chip_smoke
        from repro.configs.base import get_config
        from repro.parallel.mesh import make_mesh
        cfg = chip_smoke.pinned(get_config(chip_smoke.ARCH + "-smoke"))
        mesh = make_mesh((1, 4), ("data", "model"))
        out = chip_smoke.ep_phase(cfg, mesh, ckpt_root={str(tmp_path)!r},
                                  seq=32, batch=2)
        per = {{v["experts_per_shard"] for v in out["expert_shards"].values()}}
        assert per == {{cfg.moe.num_experts // 4}}, out
        print("EP_OK", out["loss_rel"], out["grad_norm_rel"])
        """, {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"},
        tmp_path)
    assert r.returncode == 0 and "EP_OK" in r.stdout, r.stdout + r.stderr


def test_compile_cache_location(tmp_path):
    """JAX_COMPILATION_CACHE_DIR, where set, is the only cache; otherwise
    the checkout's fixed .jax_cache/."""
    code = """
        import jax, jax.numpy as jnp
        from repro.launch.compile_cache import enable_compile_cache
        print("CACHE", enable_compile_cache())
        print("CONFIG", jax.config.jax_compilation_cache_dir)
        jax.jit(lambda x: jnp.sin(x) @ x)(jnp.ones((64, 64))).block_until_ready()
        """
    env_dir = tmp_path / "cache"
    r = _run(code, {"JAX_COMPILATION_CACHE_DIR": str(env_dir),
                    "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"},
             tmp_path)
    assert r.returncode == 0, r.stderr
    assert f"CACHE {env_dir}" in r.stdout and f"CONFIG {env_dir}" in r.stdout
    assert any(env_dir.iterdir())
    r = _run(code, {}, tmp_path)
    assert r.returncode == 0, r.stderr
    assert f"CONFIG {ROOT / '.jax_cache'}" in r.stdout


def test_main_refuses_without_tpu(tmp_path):
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=tmp_path)
    assert r.returncode == 2 and '"ok"' not in r.stdout, r.stdout + r.stderr
