"""FLOP and byte counts against hand counts at a small shape, and the
MFU numerator's independence from remat and capacity padding."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import flops  # noqa: E402
from bench.peaks import PEAKS  # noqa: E402

# d 8, 2 heads of 4 (1 kv head), 4 experts top-2 of width 3, vocab 10,
# 2 layers
M = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 1,
     "head_dim": 4, "vocab_size": 10, "num_experts": 4, "top_k": 2,
     "d_expert": 3}


def test_layer_params_by_hand():
    attn = 8 * 8 + 8 * 4 + 8 * 4 + 8 * 8      # q, k, v, o
    router = 8 * 4
    experts = 2 * 3 * 8 * 3                   # top-2 x (gate, up, down)
    assert flops.layer_matmul_params(M) == attn + router + experts == 368


def test_token_and_prefill_flops_by_hand():
    # one token at context 5: 2 layers x 2*368, attention 4*2 layers*2
    # heads*4 dims*5 keys, logits 2*8*10
    assert flops.token_flops(M, 5) == 2 * 2 * 368 + 4 * 2 * 2 * 4 * 5 + 160
    # prompt of 3: tokens see 1, 2, 3 keys; logits of the last only
    want = 3 * 2 * 2 * 368 + 4 * 2 * 2 * 4 * (1 + 2 + 3) + 160
    assert flops.prefill_flops(M, 3) == want


def test_mfu_ignores_remat_and_capacity_padding():
    """The MFU numerator is read from the model's shapes alone: keys that
    set remat or the dispatch buffer's padding (the serving cell runs at
    capacity factor 5) do not change it."""
    padded = dict(M, capacity_factor=5.0, remat="full")
    bare = dict(M, capacity_factor=1.0, remat="none")
    assert flops.prefill_flops(padded, 16) == flops.prefill_flops(bare, 16)
    assert flops.token_flops(padded, 16) == flops.token_flops(bare, 16)
    # and the expert term counts top_k experts, not all of them
    dense = dict(M, top_k=M["num_experts"])
    assert flops.layer_matmul_params(dense) - flops.layer_matmul_params(M) \
        == (4 - 2) * 3 * 8 * 3


def test_kernel_work_counts_only_hbm_bytes():
    # T 100, k 8, d 16: rows and out in HBM, the weights in VMEM
    ops = [("bf16", (100, 128), True), ("f32", (100, 8), False)]
    res = [("bf16", (100, 16), True)]
    w = flops.topk_combine_work(res, ops)
    assert w["flops"] == 2 * 100 * 8 * 16
    assert w["bytes"] == 100 * 128 * 2 + 100 * 16 * 2


def test_least_time_names_its_bound():
    p = PEAKS["TPU v5 lite"]
    assert flops.least_time(197e12, 0, p) == {"s": 1.0, "bound": "compute"}
    assert flops.least_time(0, 819e9, p) == {"s": 1.0, "bound": "memory"}
