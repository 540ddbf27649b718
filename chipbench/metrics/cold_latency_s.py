"""Mean host-clock latency, submission to resolved response, of every
invocation of the window; the cell scales to zero after each response,
so every one restores a fresh instance."""
from chipbench import readers


def read(run):
    return readers.mean_latency(run)
