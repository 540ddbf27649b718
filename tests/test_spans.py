"""Spans on the served path (`metrics.span`, `metrics.wait`).

An invocation's spans share its id across the walker's threads, the
backend pool and the guest thread; the breakdown is the ``nexus.group``
spans' durations; the ring holds a fixed number of spans; and every
modeled sleep of a served invocation is a ``nexus.wait`` span.
"""
import os
import subprocess
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import pytest

from repro.core import metrics as M
from repro.core.cache import CacheSpec
from repro.core.runtime import WorkerNode


def _spans_of(inv_id: str, since: int) -> list:
    return [s for s in M.SPANS.since(since) if s.inv == inv_id]


def _one_span(name: str) -> None:
    with M.span(name):
        pass


def _invoke_twice(system="nexus", fn="AES", **kw):
    """A cold then a warm invocation; returns (results, span start)."""
    t0 = time.monotonic_ns()
    node = WorkerNode(system, **kw)
    try:
        node.deploy(fn)
        node.seed_input(fn)
        res = [node.invoke(fn).result(timeout=60) for _ in range(2)]
    finally:
        node.shutdown()
    return res, t0


# ---------------------------------------------------------- the span API

def test_span_records_its_clock_cpu_and_attrs():
    t0 = time.monotonic_ns()
    with M.span("test.outer", inv="inv-a", bytes=3) as outer:
        with M.span("test.inner") as inner:
            sum(range(20000))
        inner_id = inner.id
    got = {s.name: s for s in M.SPANS.since(t0)}
    assert got["test.inner"].parent == outer.id
    assert got["test.inner"].inv == "inv-a"
    assert got["test.outer"].parent is None
    assert got["test.outer"].attrs == {"bytes": 3}
    assert got["test.inner"].id == inner_id
    o, i = got["test.outer"], got["test.inner"]
    assert o.t0 <= i.t0 <= i.t1 <= o.t1
    assert 0 < i.cpu <= o.cpu
    assert o.thread == i.thread == threading.get_ident()
    assert o.seconds == pytest.approx((o.t1 - o.t0) * 1e-9)


def test_carry_keeps_the_invocation_on_a_pool_thread():
    t0 = time.monotonic_ns()
    with ThreadPoolExecutor(max_workers=2) as pool:
        with M.span("test.root", inv="inv-b") as root:
            fut = pool.submit(M.carry(_one_span), "test.job")
            bare = pool.submit(_one_span, "test.bare")
            fut.result(timeout=10)
            bare.result(timeout=10)
    got = {s.name: s for s in M.SPANS.since(t0)}
    assert got["test.job"].inv == "inv-b"
    assert got["test.job"].parent == root.id
    assert got["test.job"].thread != root.thread
    # ThreadPoolExecutor copies no context by itself
    assert got["test.bare"].inv is None and got["test.bare"].parent is None


def test_ring_stays_at_its_bound():
    ring = M.SpanRing(8)
    spans = []
    for i in range(20):
        s = M.Span(f"test.ring{i}", None, {})
        s.t0 = i
        ring.add(s)
        spans.append(s)
    assert ring.since(0) == spans[-8:]
    assert ring.since(15) == spans[15:]
    assert M.SPANS._spans.maxlen == M.SPAN_RING


@pytest.mark.parametrize("seconds,slept", [(0.25, [0.25]), (0.0, []),
                                           (-1.0, [])])
def test_wait_sleeps_through_the_given_sleep(seconds, slept):
    t0 = time.monotonic_ns()
    calls = []
    M.wait("test", seconds, calls.append)
    assert calls == slept
    waits = [s for s in M.SPANS.since(t0) if s.name == "nexus.wait"]
    assert [s.attrs for s in waits] == [{"cost": "test", "s": x}
                                        for x in slept]


# ---------------------------------------------------- the served path

def test_invocation_id_and_parent_cross_walker_pool_and_guest():
    res, t0 = _invoke_twice()
    for r in res:
        spans = _spans_of(r.invocation_id, t0)
        by_id = {s.id: s for s in spans}
        roots = [s for s in spans if s.name == "nexus.invoke"]
        assert len(roots) == 1 and roots[0].parent is None
        assert roots[0].attrs["cold"] is r.cold
        # every span of the invocation links back to its root
        for s in spans:
            top = s
            while top.parent is not None:
                top = by_id[top.parent]
            assert top is roots[0], s.name
        names = {s.name for s in spans}
        assert {"nexus.group", "nexus.backend.prefetch", "nexus.guest.get", "nexus.guest.put",
                "nexus.backend.put"} <= names
        pf = next(s for s in spans if s.name == "nexus.backend.prefetch")
        assert by_id[pf.parent].name == "nexus.group"
        assert by_id[pf.parent].attrs["group"] == "fetch[0]"
        guest = next(s for s in spans if s.name == "nexus.guest.get")
        threads = {pf.thread, guest.thread, roots[0].thread}
        assert len(threads) == 3
    assert any(s.name == "nexus.restore"
               for s in _spans_of(res[0].invocation_id, t0))
    assert not any(s.name == "nexus.restore"
                   for s in _spans_of(res[1].invocation_id, t0))


@pytest.mark.parametrize("system", ["nexus", "nexus-async", "baseline"])
def test_breakdown_is_the_group_spans(system):
    res, t0 = _invoke_twice(system)
    for r in res:
        groups = {s.attrs["group"]: s.seconds
                  for s in _spans_of(r.invocation_id, t0)
                  if s.name == "nexus.group"}
        bd = {g: v for g, v in r.breakdown.items() if g != "vm_busy"}
        assert bd == groups


def test_cache_hit_copies_are_spanned_with_their_bytes():
    res, t0 = _invoke_twice(byte_scale=1 / 64, cache=CacheSpec(
        capacity_mb=64.0))
    warm = _spans_of(res[1].invocation_id, t0)
    gets = [s for s in warm if s.name == "nexus.cache.get"]
    assert any(s.attrs["bytes"] > 0 for s in gets)
    # the served path's hits copy straight from the parked payload
    assert all(s.attrs["direct"] for s in gets)
    writes = [s for s in warm if s.name == "nexus.arena.write"]
    assert writes and all(s.attrs["bytes"] > 0 for s in writes)
    hits = [s for s in warm
            if s.name == "nexus.wait" and s.attrs["cost"] == "hit"]
    assert len(hits) == sum(s.attrs["bytes"] > 0 for s in gets)


@pytest.mark.parametrize("scenario", ["EMB", "LLM-PREFILL"])
def test_every_modeled_sleep_is_a_wait_span(scenario, monkeypatch):
    """A SMOKE-size cold and warm invocation of a model scenario sleeps
    only inside `metrics.wait`, and its ``nexus.wait`` spans add up to
    what it slept."""
    from repro.core.workloads import ml_suite
    from repro.models import serving
    w = ml_suite("tiny")[scenario]
    payloads = serving.seed_payloads(scenario)
    slept, real_sleep = [], time.sleep

    def recording_sleep(seconds):
        slept.append((sys._getframe(1).f_code, seconds))
        real_sleep(seconds)

    nbytes = sum(op.size_bytes for op in (*w.profile.gets, *w.profile.puts))
    node = WorkerNode("nexus", byte_scale=1.0,
                      cache=CacheSpec(capacity_mb=2 * nbytes / 2**20))
    try:
        node.deploy(w)
        node.seed_input(scenario, payloads=payloads)
        t0 = time.monotonic_ns()
        monkeypatch.setattr(time, "sleep", recording_sleep)
        res = [node.invoke(scenario).result(timeout=120) for _ in range(2)]
        monkeypatch.setattr(time, "sleep", real_sleep)
    finally:
        node.shutdown()
    assert [r.cold for r in res] == [True, False]
    assert slept, "a served invocation sleeps its modeled costs"
    assert {code for code, _ in slept} == {M.wait.__code__}
    spans = [s for r in res for s in _spans_of(r.invocation_id, t0)]
    waits = [s.attrs["s"] for s in spans if s.name == "nexus.wait"]
    assert sum(waits) == pytest.approx(sum(t for _, t in slept))
    assert len(waits) == len(slept)
    names = {s.name for s in spans}
    assert {"nexus.handler.decode", "nexus.handler.step",
            "nexus.handler.encode"} <= names
    costs = {s.attrs["cost"] for s in spans if s.name == "nexus.wait"}
    assert {"restore", "hit"} <= costs


def test_des_import_chain_stays_jax_free():
    """Spans enter jax's TraceAnnotation only when jax is already
    loaded: importing the DES (and the span API) loads no jax."""
    code = ("import sys; import repro.core.des, repro.core.metrics; "
            "assert 'jax' not in sys.modules, 'jax loaded'")
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
