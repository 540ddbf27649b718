"""The program's own spans in a run: where each invocation's time, host
CPU and device-idle time went, layer by layer.

Two sources, both read after the window:

* the program's span ring (``repro.core.metrics.SPANS``), in this
  process: every span that started at or after the window's first
  submission. Per span: self time (its duration less that of the spans
  it encloses on its own thread), self thread-CPU, ``bytes``, and for a
  ``nexus.wait`` the modeled seconds it asked to sleep (``modeled_s``:
  its self time less this is time the thread waited to run again);
* the traced run's ``.xplane.pb``: the same spans (``nexus.*``) on the
  profiler's host plane, one line per thread, on the device's clock.
  Each device-idle interval inside ``chipbench.window`` is cut at every
  program span's start and end, and each piece goes to the innermost
  program span active at its midpoint on each thread:

  - to the `WORK` spans among them, if any (the host copies, decodes,
    encodes or steps for the invocation);
  - else to the ``nexus.wait`` spans, if any: a modeled cost, the host
    does no real work (``modeled``);
  - else to the spans that only wait on other threads (``join``).

  A piece inside no program span goes to the innermost
  ``chipbench.*`` span (``uncovered``).

`table(run)` computes this once per run and keeps it in
``run["program_spans"]``; the harness then saves it with the run. A
program without the span ring gives no table, a trace without
``nexus.*`` spans no idle split; their readers then report nothing.
"""
from __future__ import annotations

import os
from collections import defaultdict

from chipbench.trace_reduce import (DEVICE_PLANE, HOST_PLANE, NO_SPAN,
                                    OPS_LINE, SPAN_PREFIX, WINDOW_SPAN,
                                    _union, read_planes)

PROGRAM_PREFIX = "nexus."
MODELED = "nexus.wait"
#: spans whose own time is real work on their thread; the others wait
#: on their children or on other threads
WORK = frozenset({
    "nexus.cache.get", "nexus.cache.fill", "nexus.cache.put",
    "nexus.arena.write", "nexus.backend.put", "nexus.guest.put",
    "nexus.handler.decode", "nexus.handler.step", "nexus.handler.encode",
})
#: attribute that splits a span's row, by span name
SPLIT = {"nexus.wait": "cost", "nexus.group": "group"}
BACKEND = ("nexus.backend.", "nexus.cache.", "nexus.arena.")
HANDLER = ("nexus.handler.",)


def table(run):
    """The run's per-span table (cached in ``run["program_spans"]``),
    or None where the program records no spans."""
    if "program_spans" not in run:
        run["program_spans"] = _table(run)
    return run["program_spans"]


def per_invocation(run, key: str, names=None, prefixes=()):
    """Mean per invocation of column `key` over the rows named in
    `names` or starting with one of `prefixes`."""
    t = table(run)
    if t is None or not t["invocations"]:
        return None
    total = sum(row[key] for name, row in t["spans"].items()
                if (names and name in names) or name.startswith(prefixes))
    return total / t["invocations"]


def idle_modeled_share(run):
    t = table(run)
    idle = t and t.get("idle")
    if not idle or not idle["covered_s"] or not idle["total_s"]:
        return None
    return 100.0 * idle["modeled_s"] / idle["total_s"]


# ------------------------------------------------------------- recorder

def _recorded_spans(run) -> list | None:
    try:
        from repro.core import metrics
    except ImportError:
        return None
    ring = getattr(metrics, "SPANS", None)
    if ring is None or not run["invocations"]:
        return None
    t_first = min(r["t_submit"] for r in run["invocations"])
    return ring.since(int(t_first * 1e9))


def self_times(spans) -> list[tuple]:
    """``(span, self ns, self CPU ns)`` of each span: less what the
    spans it directly encloses on its own thread took."""
    by_thread = defaultdict(list)
    for s in spans:
        by_thread[s.thread].append(s)
    child_ns, child_cpu = defaultdict(int), defaultdict(int)
    for group in by_thread.values():
        stack = []
        for s in sorted(group, key=lambda s: (s.t0, -s.t1)):
            while stack and stack[-1].t1 <= s.t0:
                stack.pop()
            if stack and s.t1 <= stack[-1].t1:
                child_ns[id(stack[-1])] += s.t1 - s.t0
                child_cpu[id(stack[-1])] += s.cpu
            stack.append(s)
    return [(s, s.t1 - s.t0 - child_ns[id(s)], s.cpu - child_cpu[id(s)])
            for s in spans]


def _row() -> dict:
    return {"n": 0, "self_s": 0.0, "self_cpu_s": 0.0, "bytes": 0,
            "modeled_s": 0.0, "idle_s": 0.0}


def _table(run):
    spans = _recorded_spans(run)
    if spans is None:
        return None
    rows: dict = defaultdict(_row)
    span_cpu = 0.0
    for s, self_ns, self_cpu in self_times(spans):
        span_cpu += self_cpu * 1e-9
        keys = [s.name]
        if s.name in SPLIT:
            keys.append(f"{s.name}[{s.attrs.get(SPLIT[s.name])}]")
        for k in keys:
            row = rows[k]
            row["n"] += 1
            row["self_s"] += self_ns * 1e-9
            row["self_cpu_s"] += self_cpu * 1e-9
            row["bytes"] += int(s.attrs.get("bytes", 0))
            row["modeled_s"] += s.attrs.get("s", 0.0)
    path = _trace_file(run.get("trace_dir"))
    idle = idle_attribution(read_planes(path)) if path else None
    if idle is not None:
        for name, secs in idle.pop("by_span").items():
            rows[name]["idle_s"] += secs
    cpu = run.get("cpu_s")
    return {"invocations": len({s.inv for s in spans
                                if s.name == "nexus.invoke"}),
            "spans": dict(rows), "cpu_s": cpu, "span_cpu_s": span_cpu,
            "unattributed_cpu_s": None if cpu is None else cpu - span_cpu,
            "idle": idle}


# ---------------------------------------------------------------- trace

def _trace_file(trace_dir) -> str | None:
    if not trace_dir or not os.path.isdir(trace_dir):
        return None
    paths = [os.path.join(d, f) for d, _, fs in os.walk(trace_dir)
             for f in fs if f.endswith(".xplane.pb")]
    return max(paths, key=os.path.getmtime) if paths else None


def _innermost(spans, t):
    """The shortest of `spans` (start, end, name) that holds `t`."""
    best = None
    for s, e, n in spans:
        if s <= t < e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, n)
    return best[2] if best else None


def idle_attribution(planes) -> dict | None:
    """Device-idle seconds inside ``chipbench.window``, split by what
    the program's spans on the host plane were doing (module doc), per
    chip. None without a window, a device plane or any program span."""
    lines, bench, devices = [], [], []
    for pname, plines in planes:
        if pname == HOST_PLANE:
            for _, events in plines:
                prog = [(s, s + d, n) for n, s, d in events
                        if n.startswith(PROGRAM_PREFIX)]
                if prog:
                    lines.append(prog)
                bench += [(s, s + d, n) for n, s, d in events
                          if n.startswith(SPAN_PREFIX)]
        elif DEVICE_PLANE.match(pname):
            devices.append(dict(plines).get(OPS_LINE, []))
    window = [(s, e) for s, e, n in bench if n == WINDOW_SPAN]
    if not window or not devices or not lines:
        return None
    lo, hi = window[0]
    inner_bench = [b for b in bench if b[2] != WINDOW_SPAN]
    events = inner_bench + [p for line in lines for p in line]
    cuts = sorted({x for s, e, _ in events for x in (s, e) if lo < x < hi})
    by_span = defaultdict(float)
    kinds = defaultdict(float)
    uncovered = defaultdict(float)
    for ops in devices:
        busy = _union(((s, s + d) for _, s, d in ops), lo, hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for gs, ge in zip(edges[::2], edges[1::2]):
            inside = [x for x in cuts if gs < x < ge]
            for ps, pe in zip([gs] + inside, inside + [ge]):
                secs = (pe - ps) * 1e-9 / len(devices)
                if secs <= 0:
                    continue
                mid = (ps + pe) / 2
                active = [n for n in (_innermost(line, mid) for line in lines)
                          if n is not None]
                work = [n for n in active if n in WORK]
                waits = [n for n in active if n == MODELED]
                kind, share = (("work", work) if work else
                               ("modeled", waits) if waits else
                               ("join", active))
                if not share:
                    uncovered[_innermost(inner_bench, mid) or NO_SPAN] += secs
                    continue
                kinds[kind] += secs
                for n in share:
                    by_span[n] += secs / len(share)
    covered = sum(kinds.values())
    return {"total_s": covered + sum(uncovered.values()),
            "covered_s": covered, "work_s": kinds["work"],
            "modeled_s": kinds["modeled"], "join_s": kinds["join"],
            "uncovered": dict(uncovered), "by_span": dict(by_span)}
