"""Mean seconds per cold invocation in the ``write[k]`` groups."""
from chipbench import readers


def read(run):
    return readers.mean_groups(run, "write[", cold=True)
