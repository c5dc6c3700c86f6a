"""Mean wall time of one admission round in the window: the benchmark's
span around ``ServeEngine.prefill_step`` when it admits requests (every
chunk of every admitted prompt, run before the next decode step)."""
from bench import readers


def read(run):
    return readers.mean_span_ms(run, "admit",
                                lambda s: bool(s.info.get("requests")))
