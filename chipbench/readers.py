"""What the metric readers in ``metrics/`` share.

A reader's ``read(run)`` returns a number, or None where the run holds
nothing for it to read; the harness then leaves the metric out of the
result line. ``run`` holds:

* ``invocations``: one record per invocation of the window, with its
  host-clock ``latency_s``, ``cold`` and the plan walker's ``breakdown``
  (seconds per phase group);
* ``setup_s``, ``window_s``, ``cpu_s`` (process CPU seconds in the
  window);
* ``trace``: the `trace_reduce` result of a traced run, else None;
* ``flops_per_step``, ``step_program``, ``peaks``.
"""
from __future__ import annotations

import statistics


def latencies(run) -> list[float]:
    return [r["latency_s"] for r in run["invocations"]]


def mean_latency(run):
    lat = latencies(run)
    return statistics.fmean(lat) if lat else None


def cpu_per_invocation(run):
    n = len(run["invocations"])
    return run["cpu_s"] / n if n else None


def mean_groups(run, prefix: str, cold: bool):
    """Mean over the window's invocations of `cold`-ness of the summed
    breakdown groups whose name starts with `prefix`."""
    sums = [sum(v for g, v in r["breakdown"].items() if g.startswith(prefix))
            for r in run["invocations"]
            if r.get("breakdown") and r["cold"] == cold]
    return statistics.fmean(sums) if sums else None


def step_mfu(run):
    t = run["trace"]
    if not t or run["step_program"] not in t.get("modules", {}):
        return None
    n, secs = t["modules"][run["step_program"]]
    if n == 0 or secs <= 0:
        return None
    return (100.0 * run["flops_per_step"] * n
            / (secs * run["peaks"]["bf16_flops_per_s"]))


def idle_share(run):
    t = run["trace"]
    if not t or not t.get("window_s") or "busy_s" not in t:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
