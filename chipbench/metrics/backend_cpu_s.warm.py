"""Mean thread-CPU seconds per invocation in the backend I/O plane: the
self thread-CPU of the ``nexus.backend.*``, ``nexus.cache.*`` and
``nexus.arena.*`` spans."""
from chipbench import spans


def read(run):
    return spans.per_invocation(run, "self_cpu_s", prefixes=spans.BACKEND)
