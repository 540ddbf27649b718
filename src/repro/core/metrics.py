"""Cycle / crossing / memory accounting and spans — the measurement plane.

The paper evaluates Nexus purely in CPU cycles (split across the four
host/guest x user/kernel domains), KVM exit + vCPU-wakeup counts, and
RSS bytes. This container has no KVM, so the runtime *accounts* these
quantities explicitly: every modeled operation charges cycles to a
domain and bumps crossing counters at the host<->guest boundary (the
TPU-framework analogue of a KVM exit is a host<->device / host<->storage
boundary crossing, per DESIGN.md). The real threaded runtime and the
discrete-event density simulator share this one accounting type, so
every benchmark reports from the same books.

Spans time the real work on the served path (`span`): each records its
invocation, its own id and the id of the span that caused it, its
thread, its start and end on ``time.monotonic_ns`` and the thread's CPU
time over it, into a bounded in-memory ring (`SPANS`). Every sleep that
stands in for a modeled cost goes through `wait`, as a ``nexus.wait``
span, so what is modeled and what is real stay apart. When jax is
already loaded, each span also enters ``jax.profiler.TraceAnnotation``
and lands on the profiler's host plane, on the device trace's clock;
this module never imports jax itself (the DES import chain stays
jax-free).
"""
from __future__ import annotations

import contextvars
import functools
import itertools
import sys
import threading
import time
from collections import defaultdict, deque
from dataclasses import dataclass, field

# Cycle domains (paper Fig. 2a / Fig. 8 notation).
GUEST_USER = "guest_user"      # Gu — user handler + in-guest fabric
GUEST_KERNEL = "guest_kernel"  # Gk — guest net stack, virtio front
HOST_USER = "host_user"        # Hu — VMM userspace, Nexus backend
HOST_KERNEL = "host_kernel"    # Hk — host net stack, KVM, vhost
DOMAINS = (GUEST_USER, GUEST_KERNEL, HOST_USER, HOST_KERNEL)

# Crossing kinds (KVM-activity analogues, paper Fig. 9).
VM_EXIT = "vm_exit"            # guest->host trap (virtio kick, MMIO, ...)
VCPU_WAKEUP = "vcpu_wakeup"    # host wakes a blocked vCPU
CTRL_MSG = "ctrl_msg"          # vsock control-plane message (Nexus path)
RETRY = "retry"                # FaultPlane recovery redrive (§5)
SHED = "shed"                  # GuardRails typed rejection (overload plane)


class CycleAccount:
    """Thread-safe per-domain cycle + crossing counters.

    Cycles are in *Mcycles* (1e6 cycles) — the natural unit for the
    paper's per-invocation numbers at 2.1 GHz.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.cycles: dict[str, float] = defaultdict(float)
        self.crossings: dict[str, int] = defaultdict(int)

    def charge(self, domain: str, mcycles: float) -> None:
        assert domain in DOMAINS, domain
        with self._lock:
            self.cycles[domain] += mcycles

    def cross(self, kind: str, n: int = 1) -> None:
        with self._lock:
            self.crossings[kind] += n

    def total(self) -> float:
        with self._lock:
            return sum(self.cycles.values())

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "cycles": dict(self.cycles),
                "crossings": dict(self.crossings),
                "total": sum(self.cycles.values()),
            }

    def reset(self) -> None:
        with self._lock:
            self.cycles.clear()
            self.crossings.clear()


@dataclass
class MemoryAccount:
    """Per-component resident-set bookkeeping (paper Fig. 3/10/11).

    Components are free-form labels ("guest_os", "rpc_lib", "cloud_sdk",
    "workload", "frontend_stub", "arena", "backend", ...). Values in MB.
    """

    components: dict[str, float] = field(default_factory=dict)

    def add(self, component: str, mb: float) -> None:
        self.components[component] = self.components.get(component, 0.0) + mb

    def remove(self, component: str) -> None:
        self.components.pop(component, None)

    def total(self) -> float:
        return sum(self.components.values())

    def share(self, *components: str) -> float:
        """Fraction of total held by the named components."""
        t = self.total()
        return sum(self.components.get(c, 0.0) for c in components) / t if t else 0.0


class LatencyTrace:
    """Thread-safe list of (label, seconds) samples with percentiles."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._samples: dict[str, list[float]] = defaultdict(list)

    def record(self, label: str, seconds: float) -> None:
        with self._lock:
            self._samples[label].append(seconds)

    def percentile(self, label: str, q: float) -> float:
        with self._lock:
            xs = sorted(self._samples.get(label, []))
        if not xs:
            return float("nan")
        i = min(int(q / 100.0 * len(xs)), len(xs) - 1)
        return xs[i]

    def mean(self, label: str) -> float:
        with self._lock:
            xs = self._samples.get(label, [])
            return sum(xs) / len(xs) if xs else float("nan")


# ------------------------------------------------------------------ spans

#: spans the ring keeps; the oldest are dropped first
SPAN_RING = 32768


class Span:
    """One piece of work on one thread; a context manager (`span`).

    ``inv`` is the invocation id, taken from the enclosing span (of this
    thread, or of the thread whose context `carry` copied) unless given;
    ``parent`` is that enclosing span's id. ``t0``/``t1`` are
    ``time.monotonic_ns`` and ``cpu`` the thread's CPU nanoseconds over
    the span; ``attrs`` are small values (``bytes`` of a copy, ``cost``
    of a modeled wait) that may be added while the span is open.
    """

    __slots__ = ("name", "inv", "id", "parent", "thread", "t0", "t1", "cpu",
                 "attrs", "_c0", "_token", "_annotation")

    def __init__(self, name: str, inv: str | None, attrs: dict):
        self.name = name
        self.inv = inv
        self.attrs = attrs
        self.parent = None

    def __enter__(self) -> "Span":
        up = _CURRENT.get()
        if up is not None:
            self.parent = up.id
            if self.inv is None:
                self.inv = up.inv
        self.id = next(_SPAN_IDS)
        self.thread = threading.get_ident()
        self._token = _CURRENT.set(self)
        self._annotation = _trace_annotation(self.name, self.attrs)
        if self._annotation is not None:
            self._annotation.__enter__()
        self._c0 = time.thread_time_ns()
        self.t0 = time.monotonic_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.monotonic_ns()
        self.cpu = time.thread_time_ns() - self._c0
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
            self._annotation = None
        _CURRENT.reset(self._token)
        self._token = None
        SPANS.add(self)

    @property
    def seconds(self) -> float:
        return (self.t1 - self.t0) * 1e-9


class SpanRing:
    """The last `size` finished spans, in the order they ended."""

    def __init__(self, size: int):
        self._spans: deque[Span] = deque(maxlen=size)
        self._lock = threading.Lock()

    def add(self, s: Span) -> None:
        with self._lock:
            self._spans.append(s)

    def since(self, t_ns: int = 0) -> list[Span]:
        """The kept spans that started at or after `t_ns`
        (``time.monotonic_ns``)."""
        with self._lock:
            return [s for s in self._spans if s.t0 >= t_ns]


SPANS = SpanRing(SPAN_RING)
_CURRENT: contextvars.ContextVar[Span | None] = contextvars.ContextVar(
    "nexus_span", default=None)
_SPAN_IDS = itertools.count(1)


def span(name: str, inv: str | None = None, **attrs) -> Span:
    """``with span("nexus.cache.get", bytes=n) as s:`` times the block."""
    return Span(name, inv, attrs)


def carry(fn):
    """`fn` run in a copy of the caller's context: spans it opens on
    another thread (a pool job, a plan branch, the guest) keep the
    caller's invocation and name the caller's open span as parent."""
    return functools.partial(contextvars.copy_context().run, fn)


def wait(cost: str, seconds: float, sleep=None) -> None:
    """Sleep `seconds` standing in for the modeled cost `cost`, as a
    ``nexus.wait`` span: the one place the served path sleeps a model.
    `sleep` (default ``time.sleep``, looked up per call) lets tests
    stub the clock."""
    if seconds <= 0:
        return
    with Span("nexus.wait", None, {"cost": cost, "s": seconds}):
        (sleep or time.sleep)(seconds)


def _trace_annotation(name: str, attrs: dict):
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)
    if profiler is None:
        return None
    return profiler.TraceAnnotation(name, **attrs)
