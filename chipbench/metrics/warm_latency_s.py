"""Mean host-clock latency, submission to resolved response, of every
invocation of the window; the cell keeps its instance warm."""
from chipbench import readers


def read(run):
    return readers.mean_latency(run)
