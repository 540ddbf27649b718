"""Mean seconds per invocation of the SharedCache hit's host copies: the
self time of the ``nexus.cache.get`` (copy out of the cache) and
``nexus.arena.write`` (copy into the tenant arena) spans."""
from chipbench import spans


def read(run):
    return spans.per_invocation(
        run, "self_s", names={"nexus.cache.get", "nexus.arena.write"})
