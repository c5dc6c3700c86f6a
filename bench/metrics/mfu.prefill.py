"""Model FLOPs of the prompts admitted in the window (causal attention,
logits of the last token only) over admission wall time times the chip's
bf16 peak, in %."""
from bench import readers


def read(run):
    return readers.prefill_mfu(run)
