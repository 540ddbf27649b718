"""Mean seconds per invocation in the handler's ``nexus.handler.encode``
spans: ``serialize.dumps`` of the output."""
from chipbench import spans


def read(run):
    return spans.per_invocation(run, "self_s",
                                names={"nexus.handler.encode"})
