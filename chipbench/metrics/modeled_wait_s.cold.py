"""Mean seconds per invocation slept for modeled costs: the
``nexus.wait`` spans of every ``cost`` (restore, hit, sdk, transport,
throttle, ...), summed over threads."""
from chipbench import spans


def read(run):
    return spans.per_invocation(run, "self_s", names={"nexus.wait"})
