"""Mean wall time of one decode step in the window: the benchmark's span
around ``ServeEngine.decode_step`` (one token for every live slot)."""
from bench import readers


def read(run):
    return readers.mean_span_ms(run, "decode",
                                lambda s: bool(s.info.get("rows")))
