"""Pass 2 — Pallas kernel resource checker.

A ``pallas_call`` is a contract: the grid × BlockSpecs must (a) fit the
per-core VMEM budget (each grid step holds every operand block plus the
scratch, and the pipeline double-buffers the HBM↔VMEM operand blocks),
(b) only ever index inside the backing arrays, (c) cover every output
tile, and (d) accumulate reduced dtypes in fp32. Mosaic enforces none of
this at Python time and interpret mode only at runtime for the shapes a
test happens to pick — this pass checks the contract statically.

The checker works on :class:`KernelModel` — an analytical mirror of a
kernel's ``pallas_call`` (grid, BlockSpecs with their index maps, scratch
shapes, accumulation dtype). ``builtin_kernel_models`` mirrors the six
repo kernels (fused_mlp fwd/dgrad/wgrad, grouped_gemm, rmsnorm,
topk_combine, ssd, flash_attention) at paper-scale shapes; the mutation
harness corrupts these models and requires every corruption to be
caught.

``fused_mlp_vmem_bytes`` / ``plan_vmem_ok`` are the same footprint math
specialized to the plan knobs — ``core/adaptive.candidate_plans`` calls
``plan_vmem_ok`` so a col_slice/n_major tiling that cannot fit VMEM is
rejected statically, before any measurement.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Callable, List, Optional, Sequence, Tuple

from repro.analysis.verify.diagnostics import Diagnostic

_PASS = "kernel"

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4,
               "int8": 1, "int32": 4}
_REDUCED = ("bfloat16", "float16", "int8")

# the Pallas grid pipeline keeps the current AND next operand block in
# VMEM (double buffering); scratch is single-buffered and persists
PIPELINE_BUFFERS = 2


def _d(rule: str, loc: str, msg: str, hint: str = "",
       severity: str = "error") -> Diagnostic:
    return Diagnostic(_PASS, rule, severity, loc, msg, hint)


@dataclasses.dataclass(frozen=True)
class BlockUse:
    """One operand's blocking: the array it tiles, the block shape, and
    the grid-index -> block-index map (BlockSpec semantics: the map
    returns BLOCK indices, scaled by the block shape)."""
    name: str
    array_shape: Tuple[int, ...]
    block_shape: Tuple[int, ...]
    index_map: Callable[..., Tuple[int, ...]]
    dtype: str = "bfloat16"
    is_output: bool = False


@dataclasses.dataclass(frozen=True)
class KernelModel:
    name: str
    grid: Tuple[int, ...]
    blocks: Tuple[BlockUse, ...]
    scratch: Tuple[Tuple[Tuple[int, ...], str], ...] = ()
    accum_dtype: str = "float32"   # where partial products accumulate


def block_bytes(shape: Sequence[int], dtype: str) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n * DTYPE_BYTES[dtype]


def vmem_footprint(model: KernelModel) -> int:
    """Bytes of VMEM one grid step pins: double-buffered operand blocks
    plus single-buffered scratch."""
    io = sum(block_bytes(b.block_shape, b.dtype) for b in model.blocks)
    sc = sum(block_bytes(shape, dt) for shape, dt in model.scratch)
    return PIPELINE_BUFFERS * io + sc


def check_vmem(model: KernelModel, vmem_bytes: int) -> List[Diagnostic]:
    used = vmem_footprint(model)
    if used > vmem_bytes:
        return [_d("vmem-overflow", f"kernel:{model.name}",
                   f"VMEM footprint {used / 2**20:.1f} MiB exceeds the "
                   f"{vmem_bytes / 2**20:.1f} MiB budget "
                   f"(double-buffered operand blocks + scratch)",
                   hint="shrink the block sizes (bf/bn) or raise "
                        "n_col_blocks so each call tiles fewer columns")]
    return []


def check_index_maps(model: KernelModel,
                     max_points: int = 262144) -> List[Diagnostic]:
    """Evaluate every index map over the full grid: block offsets must
    start inside the backing array, and the output maps must visit every
    output tile at least once."""
    diags: List[Diagnostic] = []
    npoints = 1
    for d in model.grid:
        npoints *= int(d)
    if npoints > max_points:
        return [_d("grid-too-large", f"kernel:{model.name}",
                   f"grid has {npoints} points > {max_points}; "
                   "index maps unchecked", severity="warning",
                   hint="model a reduced shape with the same structure")]
    seen = {b.name: set() for b in model.blocks if b.is_output}
    for idx in itertools.product(*(range(d) for d in model.grid)):
        for b in model.blocks:
            bi = tuple(int(x) for x in b.index_map(*idx))
            if len(bi) != len(b.block_shape):
                diags.append(_d(
                    "index-map-arity", f"kernel:{model.name}:{b.name}",
                    f"index map returned {len(bi)} indices for a "
                    f"{len(b.block_shape)}-d block"))
                return diags
            for d, (i, bs, dim) in enumerate(
                    zip(bi, b.block_shape, b.array_shape)):
                if i < 0 or i * bs >= dim:
                    diags.append(_d(
                        "index-out-of-bounds",
                        f"kernel:{model.name}:{b.name}",
                        f"grid point {idx}: block index {bi} puts dim "
                        f"{d} at offset {i * bs} outside array "
                        f"{tuple(b.array_shape)}",
                        hint="index maps return BLOCK indices; check "
                             "the grid-axis ordering"))
                    if len(diags) > 8:
                        return diags
            if b.is_output:
                seen[b.name].add(bi)
    for b in model.blocks:
        if not b.is_output:
            continue
        need = itertools.product(*(
            range(-(-dim // bs))
            for dim, bs in zip(b.array_shape, b.block_shape)))
        missing = [t for t in need if t not in seen[b.name]]
        if missing:
            diags.append(_d(
                "uncovered-output-tile", f"kernel:{model.name}:{b.name}",
                f"{len(missing)} output tile(s) never written "
                f"(first: {missing[0]}): those regions return garbage",
                hint="the grid must enumerate every output block index"))
    return diags


def check_accum_dtypes(model: KernelModel) -> List[Diagnostic]:
    reduced_in = [b.name for b in model.blocks
                  if not b.is_output and b.dtype in _REDUCED]
    if reduced_in and model.accum_dtype != "float32":
        return [_d("accum-dtype", f"kernel:{model.name}",
                   f"inputs {reduced_in} are {_REDUCED}-class but the "
                   f"accumulator is {model.accum_dtype}",
                   hint="accumulate in a float32 VMEM scratch / "
                        "preferred_element_type=float32")]
    return []


def check_model(model: KernelModel, vmem_bytes: int) -> List[Diagnostic]:
    return (check_vmem(model, vmem_bytes)
            + check_index_maps(model)
            + check_accum_dtypes(model))


# ---------------------------------------------------------------------------
# Analytical mirrors of the repo's kernels
# ---------------------------------------------------------------------------


def fused_mlp_model(E=8, R=256, d=4096, f=14336, N=None, *, bm=128, bf=512,
                    bn=0, order="expert_major", glu=True,
                    dtype="bfloat16") -> KernelModel:
    """Mirror of kernels/fused_mlp.fused_mlp's grid/specs. ``N`` defaults
    to ``d`` (full-width w_down); ``bn == 0`` means one full-width tile —
    a comet col_slice call passes ``N = d/n_col`` with ``bn = 0``."""
    N = d if N is None else N
    bm, bf = min(bm, R), min(bf, f)
    bn = N if bn <= 0 else min(bn, N)
    mt, nt, ft = R // bm, N // bn, f // bf
    if order == "expert_major":
        grid = (E, mt, nt, ft)
        ix = lambda e, m, n, fi: (e, m, 0)
        iw1 = lambda e, m, n, fi: (e, 0, fi)
        iwd = lambda e, m, n, fi: (e, fi, n)
        io = lambda e, m, n, fi: (e, m, n)
    else:                                    # n_major
        grid = (nt, E, mt, ft)
        ix = lambda n, e, m, fi: (e, m, 0)
        iw1 = lambda n, e, m, fi: (e, 0, fi)
        iwd = lambda n, e, m, fi: (e, fi, n)
        io = lambda n, e, m, fi: (e, m, n)
    blocks = [BlockUse("x", (E, R, d), (1, bm, d), ix, dtype)]
    if glu:
        blocks.append(BlockUse("w_gate", (E, d, f), (1, d, bf), iw1, dtype))
    blocks.append(BlockUse("w_up", (E, d, f), (1, d, bf), iw1, dtype))
    blocks.append(BlockUse("w_down", (E, f, N), (1, bf, bn), iwd, dtype))
    blocks.append(BlockUse("out", (E, R, N), (1, bm, bn), io, dtype,
                           is_output=True))
    return KernelModel(f"fused_mlp[{order}]", grid, tuple(blocks),
                       (((bm, bn), "float32"),))


def fused_mlp_dgrad_model(E=8, R=256, d=4096, f=14336, *, bm=128, bf=256,
                          glu=True, dtype="bfloat16") -> KernelModel:
    mt, ft = R // min(bm, R), f // min(bf, f)
    bm, bf = min(bm, R), min(bf, f)
    grid = (E, mt, ft)
    ix = lambda e, m, fi: (e, m, 0)
    iw1 = lambda e, m, fi: (e, 0, fi)
    iwd = lambda e, m, fi: (e, fi, 0)
    blocks = [BlockUse("x", (E, R, d), (1, bm, d), ix, dtype)]
    if glu:
        blocks.append(BlockUse("w_gate", (E, d, f), (1, d, bf), iw1, dtype))
    blocks.append(BlockUse("w_up", (E, d, f), (1, d, bf), iw1, dtype))
    blocks.append(BlockUse("w_down", (E, f, d), (1, bf, d), iwd, dtype))
    blocks.append(BlockUse("dy", (E, R, d), (1, bm, d), ix, dtype))
    blocks.append(BlockUse("dx", (E, R, d), (1, bm, d), ix, dtype,
                           is_output=True))
    return KernelModel("fused_mlp_dgrad", grid, tuple(blocks),
                       (((bm, d), "float32"),))


def fused_mlp_wgrad_model(E=8, R=256, d=4096, f=14336, *, bm=128, bf=128,
                          glu=True, dtype="bfloat16") -> KernelModel:
    bm, bf = min(bm, R), min(bf, f)
    grid = (E, f // bf, R // bm)
    ix = lambda e, fi, m: (e, m, 0)
    iw1 = lambda e, fi, m: (e, 0, fi)
    iwd = lambda e, fi, m: (e, fi, 0)
    n_l0 = ("w_gate", "w_up") if glu else ("w_up",)
    blocks = [BlockUse("x", (E, R, d), (1, bm, d), ix, dtype)]
    blocks += [BlockUse(n, (E, d, f), (1, d, bf), iw1, dtype) for n in n_l0]
    blocks.append(BlockUse("w_down", (E, f, d), (1, bf, d), iwd, dtype))
    blocks.append(BlockUse("dy", (E, R, d), (1, bm, d), ix, dtype))
    blocks += [BlockUse("d" + n, (E, d, f), (1, d, bf), iw1, dtype,
                        is_output=True) for n in n_l0]
    blocks.append(BlockUse("dw_down", (E, f, d), (1, bf, d), iwd, dtype,
                           is_output=True))
    scratch = tuple(((d, bf), "float32") for _ in n_l0) \
        + (((bf, d), "float32"),)
    return KernelModel("fused_mlp_wgrad", grid, tuple(blocks), scratch)


def grouped_gemm_model(E=8, M=256, N=4096, K=512, *, bm=128, bn=128,
                       bk=512, order="expert_major",
                       dtype="bfloat16") -> KernelModel:
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    mt, nt, kt = M // bm, N // bn, K // bk
    if order == "expert_major":
        grid = (E, mt, nt, kt)
        lhs = lambda e, m, n, k: (e, m, k)
        rhs = lambda e, m, n, k: (e, k, n)
        out = lambda e, m, n, k: (e, m, n)
    else:
        grid = (nt, E, mt, kt)
        lhs = lambda n, e, m, k: (e, m, k)
        rhs = lambda n, e, m, k: (e, k, n)
        out = lambda n, e, m, k: (e, m, n)
    return KernelModel(
        f"grouped_gemm[{order}]", grid,
        (BlockUse("lhs", (E, M, K), (1, bm, bk), lhs, dtype),
         BlockUse("rhs", (E, K, N), (1, bk, bn), rhs, dtype),
         BlockUse("out", (E, M, N), (1, bm, bn), out, dtype,
                  is_output=True)),
        (((bm, bn), "float32"),))


def rmsnorm_model(T=4096, d=4096, *, bt=256,
                  dtype="bfloat16") -> KernelModel:
    return KernelModel(
        "rmsnorm", (T // bt,),
        (BlockUse("x", (T, d), (bt, d), lambda i: (i, 0), dtype),
         BlockUse("scale", (d,), (d,), lambda i: (0,), dtype),
         BlockUse("out", (T, d), (bt, d), lambda i: (i, 0), dtype,
                  is_output=True)),
        accum_dtype="float32")   # fp32 row statistics in-body


def topk_combine_model(T=4096, k=2, d=4096, *, bt=128,
                       dtype="bfloat16") -> KernelModel:
    return KernelModel(
        "topk_combine", (T // bt,),
        (BlockUse("rows", (T, k * d), (bt, k * d), lambda i: (i, 0),
                  dtype),
         BlockUse("weights", (T, k), (bt, k), lambda i: (i, 0), "float32"),
         BlockUse("out", (T, d), (bt, d), lambda i: (i, 0), dtype,
                  is_output=True)),
        accum_dtype="float32")   # fp32 accumulator in-body


def ssd_model(B=4, nh=24, NC=16, Q=256, hd=64, ds=128,
              dtype="float32") -> KernelModel:
    return KernelModel(
        "ssd", (B * nh, NC),
        (BlockUse("x", (B * nh, NC * Q, hd), (1, Q, hd),
                  lambda bh, c: (bh, c, 0), dtype),
         BlockUse("dt", (B * nh, NC * Q, 1), (1, Q, 1),
                  lambda bh, c: (bh, c, 0), "float32"),
         BlockUse("A", (B * nh, 1), (1, 1), lambda bh, c: (bh, 0),
                  "float32"),
         BlockUse("Bm", (B * nh, NC * Q, ds), (1, Q, ds),
                  lambda bh, c: (bh, c, 0), dtype),
         BlockUse("Cm", (B * nh, NC * Q, ds), (1, Q, ds),
                  lambda bh, c: (bh, c, 0), dtype),
         BlockUse("D", (B * nh, 1), (1, 1), lambda bh, c: (bh, 0),
                  "float32"),
         BlockUse("out", (B * nh, NC * Q, hd), (1, Q, hd),
                  lambda bh, c: (bh, c, 0), dtype, is_output=True)),
        (((ds, hd), "float32"),))


def flash_attention_model(B=2, Hq=32, Hkv=8, Sq=2048, Sk=2048, hd=128,
                          *, bq=128, bk=128,
                          dtype="bfloat16") -> KernelModel:
    rep = Hq // Hkv
    nq, nk = Sq // bq, Sk // bk

    def kv_map(bh, qi, ki):
        b = bh // Hq
        h = bh % Hq
        return (b * Hkv + h // rep, ki, 0)

    qmap = lambda bh, qi, ki: (bh, qi, 0)
    return KernelModel(
        "flash_attention", (B * Hq, nq, nk),
        (BlockUse("q", (B * Hq, Sq, hd), (1, bq, hd), qmap, dtype),
         BlockUse("k", (B * Hkv, Sk, hd), (1, bk, hd), kv_map, dtype),
         BlockUse("v", (B * Hkv, Sk, hd), (1, bk, hd), kv_map, dtype),
         BlockUse("out", (B * Hq, Sq, hd), (1, bq, hd), qmap, dtype,
                  is_output=True)),
        (((bq, 1), "float32"), ((bq, 1), "float32"),
         ((bq, hd), "float32")))


def builtin_kernel_models() -> List[KernelModel]:
    """All six kernels at paper-scale shapes, both traversal orders where
    the kernel has them."""
    return [
        fused_mlp_model(order="expert_major"),
        fused_mlp_model(order="n_major", N=1024, R=1024),  # comet col_slice
        fused_mlp_dgrad_model(),
        fused_mlp_wgrad_model(),
        grouped_gemm_model(order="expert_major"),
        grouped_gemm_model(order="n_major"),
        rmsnorm_model(),
        topk_combine_model(),                   # mixtral-style k=2, full d
        topk_combine_model(k=8, d=1024),        # qwen3-style k=8, col block
        ssd_model(),
        flash_attention_model(),
    ]


def check_builtin_kernels(vmem_bytes: Optional[int] = None
                          ) -> List[Diagnostic]:
    if vmem_bytes is None:
        from repro.core.adaptive import TPU_V5E
        vmem_bytes = TPU_V5E.vmem_bytes
    diags: List[Diagnostic] = []
    for model in builtin_kernel_models():
        diags.extend(check_model(model, vmem_bytes))
    return diags


# ---------------------------------------------------------------------------
# Plan-knob VMEM gate (core/adaptive.candidate_plans hook)
# ---------------------------------------------------------------------------


def fused_mlp_vmem_bytes(N: int, K: int, n_col: int, *, glu: bool = True,
                         bm: int = 128, bf: int = 512,
                         bytes_per_elt: int = 2) -> int:
    """VMEM footprint of one comet col-sliced fused_mlp call under a plan:
    the call tiles ``N/n_col`` output columns full-width (``bn=0``).
    Duck-typed on ints only so core/adaptive can import it cycle-free."""
    bn = max(1, N // max(1, n_col))
    bfe = min(bf, K)
    n_l0 = 2 if glu else 1
    io = (bm * N                       # x block (1, bm, d)
          + n_l0 * N * bfe             # w_gate/w_up blocks (1, d, bf)
          + bfe * bn                   # w_down block (1, bf, bn)
          + bm * bn) * bytes_per_elt   # out block (1, bm, bn)
    return PIPELINE_BUFFERS * io + bm * bn * 4   # + fp32 scratch


def plan_vmem_ok(s, plan, hw) -> bool:
    """Whether ``plan``'s implied kernel tiling fits ``hw.vmem_bytes``.
    Non-Pallas backends stream through XLA and are never rejected."""
    budget = getattr(hw, "vmem_bytes", 0)
    if not budget or plan.gemm_impl != "pallas_fused":
        return True
    n_col = (max(1, plan.n_col_blocks)
             if plan.impl in ("comet", "comet_hier") else 1)
    return fused_mlp_vmem_bytes(
        s.N, s.K, n_col, glu=s.glu,
        bytes_per_elt=s.bytes_per_elt) <= budget


def check_candidate_plans(shapes=None, hw=None) -> List[Diagnostic]:
    """Property check: ``candidate_plans`` must never emit a tiling that
    overflows the hardware's VMEM budget."""
    from repro.core import adaptive as A
    hw = hw or A.TPU_V5E
    if shapes is None:
        shapes = [
            A.MoEShape(M=8192, N=4096, K=14336, E=8, topk=2, ep=8, etp=1),
            A.MoEShape(M=8192, N=2048, K=1408, E=64, topk=4, ep=8, etp=1),
            A.MoEShape(M=4096, N=16384, K=4096, E=16, topk=2, ep=8, etp=1),
        ]
    diags: List[Diagnostic] = []
    for s in shapes:
        for p in A.candidate_plans(s, include_graph=True, hw=hw):
            if not plan_vmem_ok(s, p, hw):
                diags.append(_d(
                    "vmem-overflow", f"plan:N{s.N}:K{s.K}",
                    f"candidate_plans emitted {p.impl}/"
                    f"{p.gemm_impl} n_col={p.n_col_blocks} whose tiling "
                    f"needs more than {hw.vmem_bytes / 2**20:.0f} MiB",
                    hint="candidate_plans must filter through "
                         "plan_vmem_ok"))
    return diags


# ---------------------------------------------------------------------------
# Legalization fixed-point
# ---------------------------------------------------------------------------


def check_legalize_fixed_point(d_models=(1536, 2048, 4096, 7168, 18432),
                               eps=(1, 2, 4, 8, 16),
                               max_knob: int = 12) -> List[Diagnostic]:
    """legalize ∘ legalize == legalize over the knob grid: a legalized
    plan must be a fixed point, or the tuner's persisted knobs and the
    transport's executed knobs could disagree (PR 3's silent
    re-legalization bug, made impossible)."""
    from repro.core import adaptive as A
    diags: List[Diagnostic] = []
    for d_model in d_models:
        for ep in eps:
            for n_col in range(1, max_knob + 1):
                for rg in range(1, max_knob + 1):
                    p1 = A.legalize_plan(
                        A.Plan("comet", rg, n_col, "xla"), d_model, ep)
                    p2 = A.legalize_plan(p1, d_model, ep)
                    if p2 != p1:
                        diags.append(_d(
                            "legalize-not-fixed-point",
                            f"plan:d{d_model}:ep{ep}",
                            f"legalize({n_col},{rg}) -> "
                            f"({p1.n_col_blocks},{p1.ring_group}) -> "
                            f"({p2.n_col_blocks},{p2.ring_group}); "
                            "legalization must be idempotent"))
                    if (p1.n_col_blocks < 1 or d_model % p1.n_col_blocks
                            or p1.ring_group < 1
                            or max(1, ep) % p1.ring_group):
                        diags.append(_d(
                            "illegal-knob", f"plan:d{d_model}:ep{ep}",
                            f"legalized knobs ({p1.n_col_blocks},"
                            f"{p1.ring_group}) do not divide "
                            f"(d_model={d_model}, ep={ep})"))
    return diags
