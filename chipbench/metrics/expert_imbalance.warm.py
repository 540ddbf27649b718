"""How unevenly the routing loads the held experts: the mean over the
window's invocations of ``expert_tokens_max x experts / expert_tokens``
on their ``nexus.handler.step`` spans, the busiest held expert's
assignments (summed over layers) over the held experts' mean. 1.0 is an
even load; the step waits on its busiest expert's group. A program whose
step spans carry no expert counters gives nothing."""
import statistics

from chipbench import spans


def read(run):
    recorded = spans._recorded_spans(run) or []
    ratios = [s.attrs["expert_tokens_max"] * s.attrs["experts"]
              / s.attrs["expert_tokens"] for s in recorded
              if s.name == "nexus.handler.step"
              and s.attrs.get("expert_tokens")]
    return statistics.fmean(ratios) if ratios else None
