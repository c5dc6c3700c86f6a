"""``lead_dense_moe`` at a size the CPU runs (``tiny.py``)."""

TINY_MODEL = {"hidden_size": 128, "intermediate_size": 256,
              "moe_intermediate_size": 64, "vocab_size": 512}


def apply(c: dict) -> None:
    c.update(TINY_MODEL)
    p = c["program"]
    p["changes"] = dict(p["changes"], d_model=128, d_ff=256, vocab_size=512,
                        param_dtype="float32", compute_dtype="float32",
                        attn={"n_heads": 4, "n_kv_heads": 2, "head_dim": 32,
                              "q_block": 32, "kv_block": 32})
    p["moe_knobs"] = dict(p["moe_knobs"], d_expert=64)
    # float32, as moe_lm's tiny size is, for the same reason
    p["param_dtype"] = "float32"
    # limits from its CPU readings on seeds 1-5 and 2**33+7, widest /
    # mean: sound 0.0 / 0.0, fp8 control 0.49-1.59 / 0.032-0.062, altered
    # token 5.43-6.30 / 1.91-2.05
    c["check"] = dict(c["check"], widest_logit_gap_limit=0.5,
                      mean_logit_gap_limit=0.005)
