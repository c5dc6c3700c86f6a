"""Share of the traced window in which no operation runs on the chip:
1 - (union of device-op intervals) / window, in %."""
from bench import readers


def read(run):
    return readers.idle_share(run)
