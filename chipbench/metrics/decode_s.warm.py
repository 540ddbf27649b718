"""Mean seconds per invocation in the handler's ``nexus.handler.decode``
spans: ``serialize.loads`` of each input with its host-to-device copy."""
from chipbench import spans


def read(run):
    return spans.per_invocation(run, "self_s",
                                names={"nexus.handler.decode"})
