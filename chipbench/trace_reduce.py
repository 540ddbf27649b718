"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

* ``busy_s``: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), inside
  the traced window, averaged over the chips;
* ``window_s``: the traced window, the benchmark's own
  ``chipbench.window`` span on the host (else the extent of the trace);
* ``modules``: per compiled program (``XLA Modules`` line, the name
  without its ``(<id>)`` suffix), how many times it ran and its device
  seconds;
* ``device_ops``: the ten operations that took most device time, each
  by its HLO name (``fusion.120``, not the instruction's whole text);
  an op that holds others (a ``while`` over the layers) counts their
  time too;
* ``idle_gaps``: device idle time inside the window, attributed to the
  innermost ``chipbench.*`` host span that covers the middle of each
  gap, summed per span name, the ten largest;
* ``planes``: each plane's name with its lines' names, so that a trace
  whose device planes or lines are named otherwise can be read by eye.
"""
from __future__ import annotations

import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"
NO_SPAN = "outside any chipbench span"
TOP = 10


def _union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged [start, end) intervals clipped to [lo, hi)."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def _op_name(name: str) -> str:
    """``%fusion.1 = bf16[8]{0} fusion(...)`` -> ``fusion.1``."""
    return name.split(" = ", 1)[0].lstrip("%")


def reduce_planes(planes) -> dict:
    """`planes`: iterable of (plane name, [(line name, [(event name,
    start_ns, duration_ns), ...]), ...])."""
    spans, devices, names = [], [], {}
    for pname, lines in planes:
        names[pname] = [ln for ln, _ in lines]
        if pname == HOST_PLANE:
            for _, events in lines:
                spans += [(n, s, s + d) for n, s, d in events
                          if n.startswith(SPAN_PREFIX)]
        elif DEVICE_PLANE.match(pname):
            devices.append(dict(lines))
    window = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if window:
        lo, hi = window[0]
    else:
        ends = [(s, s + d) for dev in devices
                for ev in dev.values() for _, s, d in ev]
        ends += [(s, e) for _, s, e in spans]
        if not ends:
            return {"planes": names}
        lo, hi = min(s for s, _ in ends), max(e for _, e in ends)
    out = {"window_s": (hi - lo) * 1e-9, "chips": len(devices),
           "planes": names}
    if not devices:
        return out
    busy, ops, modules = 0.0, defaultdict(float), {}
    gaps = defaultdict(float)
    inner = sorted(((s, e, n) for n, s, e in spans if n != WINDOW_SPAN),
                   key=lambda t: t[1] - t[0])
    for dev in devices:
        events = dev.get(OPS_LINE, [])
        merged = _union(((s, s + d) for _, s, d in events), lo, hi)
        busy += sum(e - s for s, e in merged)
        for n, s, d in events:
            ops[_op_name(n)] += d * 1e-9
        for n, s, d in dev.get(MODULES_LINE, []):
            m = modules.setdefault(_module_name(n), [0, 0.0])
            m[0] += 1
            m[1] += d * 1e-9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge <= gs:
                continue
            mid = (gs + ge) / 2
            name = next((n for s, e, n in inner if s <= mid < e), NO_SPAN)
            gaps[name] += (ge - gs) * 1e-9 / len(devices)
    out["busy_s"] = busy * 1e-9 / len(devices)
    out["modules"] = modules
    out["device_ops"] = sorted(([n, s / len(devices)] for n, s in ops.items()),
                               key=lambda t: -t[1])[:TOP]
    out["idle_gaps"] = sorted(([n, s] for n, s in gaps.items()),
                              key=lambda t: -t[1])[:TOP]
    return out


def read_planes(path: str):
    """The planes of an ``.xplane.pb`` file, as `reduce_planes` takes them."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    return [(p.name, [(ln.name, [(ev.name, ev.start_ns, ev.duration_ns)
                                 for ev in ln.events])
                      for ln in p.lines])
            for p in pd.planes]


def reduce(path: str) -> dict:
    return reduce_planes(read_planes(path))
