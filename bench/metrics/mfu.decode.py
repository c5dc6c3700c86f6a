"""Model FLOPs of the tokens decoded in the window, each attending over
its own cache, over decode-step wall time times the chip's bf16 peak, in
%."""
from bench import readers


def read(run):
    return readers.decode_mfu(run)
