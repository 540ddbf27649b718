"""Mean seconds per cold invocation in the ``compute[0]`` group: payload
decode, host-to-device copy, the device step and the re-encode."""
from chipbench import readers


def read(run):
    return readers.mean_groups(run, "compute[", cold=True)
