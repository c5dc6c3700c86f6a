"""``topk_combine`` (kernels/topk_combine.py) against its roofline, in %:
the roofline-least time of each of its calls in the traced window, from
the shapes its trace event carries (bench/flops.py), over their summed
trace time; the median over chips. The kernel is bound by memory: it
reads k bf16 rows and writes one per token."""
from bench import flops, readers
from bench.trace import shapes


def read(run):
    return readers.roofline_share(
        run, r"topk_combine",
        lambda op: flops.topk_combine_work(*shapes(op.text)))
