"""The MoE path's Pallas kernels compiled by the TPU compiler for a
described v5e, with no chip attached: what interpret mode cannot see (tile
alignment, Mosaic lowering limits, scoped VMEM) fails here at no chip time.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports every
test file. Kernels are compiled with ``interpret=False`` because
``jax.default_backend()`` still says cpu here.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.analysis.verify import kernel_check as KC
from repro.core import adaptive as A
from repro.kernels import VMEM_LIMIT_BYTES, ops
from repro.kernels import fused_mlp as FM

BF16 = jnp.bfloat16
# (d_model, d_expert, local experts, dispatched rows per expert)
WIDTHS = {"granite-moe-3b-a800m": (1536, 512, 40, 512),
          "qwen2-moe-2.7b": (2048, 1408, 60, 512)}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def sds(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype=BF16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)


def _compile(fn, *args, **static) -> str:
    return fn.lower(*args, **static).compile().as_text()


def _expert_args(sds, width):
    d, f, E, R = WIDTHS[width]
    w = {"w_gate": sds((E, d, f)), "w_up": sds((E, d, f)),
         "w_down": sds((E, f, d))}
    return sds((E, R, d)), w, sds((E, R, d)), d


@pytest.mark.parametrize("T,k,d", [(2048, 8, 1536), (8, 8, 1536),
                                   (2048, 8, 384)])
def test_topk_combine_compiles(sds, T, k, d):
    txt = _compile(ops.topk_combine_diff, sds((T, k, d)),
                   sds((T, k), jnp.float32), interpret=False)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("kind", ["fwd", "fwd_col_block", "dgrad", "wgrad"])
def test_fused_mlp_compiles_with_transport_tiling(sds, width, kind):
    """The calls core/transport.py makes, with the tilings it passes (the
    wrapper defaults), at published widths."""
    x, w, dy, d = _expert_args(sds, width)
    if kind == "fwd":
        txt = _compile(ops.fused_mlp, x, w, "swiglu", interpret=False)
    elif kind == "fwd_col_block":
        txt = _compile(ops.fused_mlp, x, w, "swiglu",
                       col_slice=(0, d // 4), order="n_major",
                       interpret=False)
    elif kind == "dgrad":
        txt = _compile(ops.fused_mlp_dgrad, x, w, dy, "swiglu",
                       interpret=False)
    else:
        txt = _compile(ops.fused_mlp_wgrad, x, w, dy, "swiglu",
                       interpret=False)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("order", ["expert_major", "n_major"])
def test_grouped_gemm_compiles(sds, order):
    d, f, E, R = WIDTHS["granite-moe-3b-a800m"]
    txt = _compile(ops.grouped_gemm, sds((E, R, d)), sds((E, d, f)),
                   order=order, interpret=False)
    assert "tpu_custom_call" in txt


def test_vmem_gate_admits_only_what_compiles(sds):
    """plan_vmem_ok judges a tiling against Hardware.vmem_bytes, which is
    the limit the kernels are compiled with. The largest tiling it admits
    among the paper shapes (mixtral width, one column block, 30 MiB
    modeled) compiles."""
    assert A.TPU_V5E.vmem_bytes == VMEM_LIMIT_BYTES
    s = A.MoEShape(M=4096, N=4096, K=14336, E=8, topk=2, ep=8, etp=1)
    plan = A.Plan("comet", 1, 1, "pallas_fused")
    assert KC.plan_vmem_ok(s, plan, A.TPU_V5E)
    assert KC.fused_mlp_vmem_bytes(s.N, s.K, 1) > 0.9 * VMEM_LIMIT_BYTES
    w = {"w_gate": sds((1, s.N, s.K)), "w_up": sds((1, s.N, s.K)),
         "w_down": sds((1, s.K, s.N))}
    txt = _compile(ops.fused_mlp, sds((1, 256, s.N)), w, "swiglu",
                   interpret=False)
    assert "tpu_custom_call" in txt


def test_wgrad_vmem_model_agrees_with_mosaic(sds):
    """The wgrad mirror in kernel_check and Mosaic agree on both sides of
    the limit: bf=512 at qwen2-moe width is over it and refused, the
    default WGRAD_BF is under it and compiles."""
    d, f, E, R = WIDTHS["qwen2-moe-2.7b"]
    x, w, dy, _ = _expert_args(sds, "qwen2-moe-2.7b")
    for bf, fits in ((512, False), (FM.WGRAD_BF, True)):
        model = KC.fused_mlp_wgrad_model(E=E, R=R, d=d, f=f, bf=bf)
        assert (KC.vmem_footprint(model) <= VMEM_LIMIT_BYTES) == fits
        if fits:
            _compile(ops.fused_mlp_wgrad, x, w, dy, "swiglu", bf=bf,
                     interpret=False)
        else:
            with pytest.raises(Exception, match="vmem"):
                _compile(ops.fused_mlp_wgrad, x, w, dy, "swiglu", bf=bf,
                         interpret=False)
