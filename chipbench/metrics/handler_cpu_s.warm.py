"""Mean thread-CPU seconds per invocation in the handler cores: the self
thread-CPU of the ``nexus.handler.*`` spans."""
from chipbench import spans


def read(run):
    return spans.per_invocation(run, "self_cpu_s", prefixes=spans.HANDLER)
