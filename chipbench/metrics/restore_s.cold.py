"""Mean seconds per cold invocation in the snapshot restore group
(``restore`` of the plan walker's breakdown)."""
from chipbench import readers


def read(run):
    return readers.mean_groups(run, "restore", cold=True)
