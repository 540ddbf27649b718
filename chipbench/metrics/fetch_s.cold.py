"""Mean seconds per cold invocation in the ``fetch[i]`` groups."""
from chipbench import readers


def read(run):
    return readers.mean_groups(run, "fetch[", cold=True)
