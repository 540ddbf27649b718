"""The process's user and system CPU seconds over the window
(``getrusage``), over the invocations submitted in it."""
from chipbench import readers


def read(run):
    return readers.cpu_per_invocation(run)
