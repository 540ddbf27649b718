"""The program's spans read for the per-layer metrics (`chipbench.spans`).

A small trace is recorded here by the JAX profiler on the CPU: inside
``chipbench.window``, a stage span, then an invocation whose plan group
waits while a pool thread copies (``nexus.cache.get``) and sleeps a
modeled cost (``nexus.wait``), then a step, then a readback span. The
CPU has no ``/device:TPU`` plane, so one is planted: the device is busy
exactly during the step, and idle everywhere else in the window.
"""
import glob
import threading
import time

import pytest

from chipbench import harness, spans
from chipbench.trace_reduce import read_planes

MS = 0.001


def _busy(seconds: float) -> None:
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        pass


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """(trace dir, the record of a one-invocation run)."""
    import jax
    from jax.profiler import TraceAnnotation
    from repro.core import metrics as M

    trace_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(trace_dir)
    with TraceAnnotation("chipbench.window"):
        with TraceAnnotation("chipbench.stage"):
            time.sleep(10 * MS)
        t_submit = time.monotonic()

        def pool_job():
            with M.span("nexus.backend.prefetch"):
                with M.span("nexus.cache.get", bytes=1024) as s:
                    _busy(20 * MS)
                    s.attrs["bytes"] = 1024
                M.wait("hit", 30 * MS)

        with M.span("nexus.invoke", inv="inv-0"):
            with M.span("nexus.group", group="fetch[0]"):
                t = threading.Thread(target=M.carry(pool_job))
                t.start()
                t.join(timeout=10)
            with M.span("nexus.handler.step"):
                time.sleep(15 * MS)
        with TraceAnnotation("chipbench.readback"):
            time.sleep(10 * MS)
    jax.profiler.stop_trace()
    run = {"invocations": [{"t_submit": t_submit}], "cpu_s": 1.0,
           "trace_dir": trace_dir}
    return trace_dir, run


def _device_planes(trace_dir, busy_span="nexus.handler.step"):
    """The recorded planes, with a device busy exactly during
    `busy_span`."""
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    planes = read_planes(path)
    host = dict(planes)["/host:CPU"]
    ops = [("fusion.1", s, d) for _, events in host for n, s, d in events
           if n == busy_span]
    return planes + [("/device:TPU:0", [("XLA Ops", ops)])]


def _window(planes):
    host = dict(planes)["/host:CPU"]
    return {n: (s, s + d) for _, events in host for n, s, d in events
            if n.startswith(("chipbench.", "nexus."))}


def test_idle_goes_to_the_program_spans_and_the_rest_is_named(recorded):
    trace_dir, _ = recorded
    planes = _device_planes(trace_dir)
    idle = spans.idle_attribution(planes)
    sp = _window(planes)
    lo, hi = sp["chipbench.window"]
    step = sp["nexus.handler.step"]
    total = (hi - lo - (step[1] - step[0])) * 1e-9
    assert idle["total_s"] == pytest.approx(total, rel=1e-6)
    assert idle["covered_s"] + sum(idle["uncovered"].values()) == \
        pytest.approx(total, rel=1e-6)
    # the stage and readback spans hold no program span
    assert set(idle["uncovered"]) <= {"chipbench.stage", "chipbench.readback",
                                     "outside any chipbench span"}
    assert idle["uncovered"]["chipbench.stage"] == pytest.approx(
        10 * MS, abs=3 * MS)
    # the copy is work, the hit's sleep modeled: the plan group only
    # waits on the pool thread, so it takes neither
    get, wait = sp["nexus.cache.get"], sp["nexus.wait"]
    assert idle["work_s"] == pytest.approx((get[1] - get[0]) * 1e-9,
                                           rel=1e-6)
    assert idle["modeled_s"] == pytest.approx((wait[1] - wait[0]) * 1e-9,
                                              rel=1e-6)
    assert idle["by_span"]["nexus.cache.get"] == pytest.approx(
        idle["work_s"])
    assert idle["by_span"]["nexus.wait"] == pytest.approx(idle["modeled_s"])
    assert "nexus.handler.step" not in idle["by_span"]


def test_trace_without_program_spans_splits_no_idle():
    from chipbench.tests.test_trace_reduce import TPU_RECORDED
    assert spans.idle_attribution(read_planes(TPU_RECORDED)) is None


def test_table_has_self_time_cpu_bytes_and_idle(recorded, monkeypatch):
    trace_dir, run = recorded
    monkeypatch.setattr(spans, "read_planes",
                        lambda _path: _device_planes(trace_dir))
    run = dict(run)
    t = spans.table(run)
    assert run["program_spans"] is t
    assert t["invocations"] == 1
    rows = t["spans"]
    assert rows["nexus.cache.get"]["bytes"] == 1024
    assert rows["nexus.cache.get"]["self_cpu_s"] >= 15 * MS
    assert rows["nexus.wait[hit]"]["n"] == 1
    assert rows["nexus.wait"]["self_s"] == pytest.approx(30 * MS, abs=10 * MS)
    assert rows["nexus.wait"]["self_cpu_s"] < 5 * MS
    assert rows["nexus.wait[hit]"]["modeled_s"] == pytest.approx(30 * MS)
    # the group's self time excludes nothing on its own thread but the
    # pool job runs on another: its self time covers the whole wait
    assert rows["nexus.group[fetch[0]]"]["self_s"] >= 45 * MS
    assert rows["nexus.backend.prefetch"]["self_s"] < 5 * MS
    assert t["unattributed_cpu_s"] == pytest.approx(1.0 - t["span_cpu_s"])
    assert rows["nexus.wait"]["idle_s"] == pytest.approx(
        t["idle"]["modeled_s"])


def test_readers_report_every_new_metric(recorded, monkeypatch):
    trace_dir, run = recorded
    monkeypatch.setattr(spans, "read_planes",
                        lambda _path: _device_planes(trace_dir))
    run = dict(run)
    names = [m["name"] for cell in ("yi-emb-warm", "yi-prefill-coldvm")
             for m in harness.load_cell(cell).metrics
             if m["name"].split(".")[0] in {
                 "hit_copy_s", "modeled_wait_s", "decode_s", "encode_s",
                 "backend_cpu_s", "handler_cpu_s", "idle_modeled_share"}]
    assert len(names) == 14
    got = {n: harness._reader(n)(run) for n in names}
    assert all(v is not None for v in got.values()), got
    assert got["hit_copy_s.warm"] == pytest.approx(20 * MS, abs=10 * MS)
    assert got["decode_s.cold"] == 0.0
    share = got["idle_modeled_share.warm"]
    assert 0 < share < 100


def test_program_without_spans_reports_nothing(recorded, monkeypatch):
    """The parent program has no span ring: every reader gives None."""
    from repro.core import metrics as M
    _, run = recorded
    monkeypatch.delattr(M, "SPANS")
    run = dict(run)
    for name in ("hit_copy_s.warm", "backend_cpu_s.cold",
                 "idle_modeled_share.cold"):
        assert harness._reader(name)(run) is None
    assert run["program_spans"] is None
