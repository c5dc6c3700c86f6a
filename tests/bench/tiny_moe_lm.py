"""``moe_lm`` at a size the CPU runs (``tiny.py``)."""

TINY_MODEL = {
    "num_hidden_layers": 2, "hidden_size": 128, "intermediate_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_local_experts": 8, "num_experts_per_tok": 2, "vocab_size": 512,
}
TINY_PROGRAM_CHANGES = {
    "n_layers": 2, "d_model": 128, "vocab_size": 512,
    "attn": {"n_heads": 4, "n_kv_heads": 2, "head_dim": 32,
             "q_block": 32, "kv_block": 32},
}
TINY_MOE = {"num_experts": 8, "top_k": 2, "d_expert": 64,
            # num_experts / top_k: no expert can overflow, as at full size
            "capacity_factor": 4.0}


def apply(c: dict) -> None:
    c.update(TINY_MODEL)
    c["program"]["changes"] = dict(c["program"]["changes"],
                                   **TINY_PROGRAM_CHANGES)
    c["program"]["moe_knobs"] = dict(c["program"]["moe_knobs"], **TINY_MOE)
    # float32: at d 128 bf16 rounding flips the top-2-of-8 routing of
    # enough tokens that a sound run's widest gap over every served
    # request (0.0-0.44 on seeds 1-5 and 2**33+7) reaches the fp8
    # control's (0.28-0.78). The full size is bf16.
    c["program"]["param_dtype"] = "float32"
    c["program"]["changes"].update(param_dtype="float32",
                                   compute_dtype="float32")
    # limits for this size, set as at full size (the widest between sound
    # runs and an altered token, the mean between sound runs and the fp8
    # control) from its CPU readings on seeds 1-5 and 2**33+7, widest /
    # mean: sound 0.0 / 0.0, fp8 control 0.42-0.78 / 0.023-0.030, altered
    # token 1.10-1.33 / 0.41-0.48
    c["check"] = dict(c["check"], widest_logit_gap_limit=0.5,
                      mean_logit_gap_limit=0.005)
