"""Mean seconds per warm invocation in the ``fetch[i]`` groups."""
from chipbench import readers


def read(run):
    return readers.mean_groups(run, "fetch[", cold=False)
