"""The reduction from a profiler trace to busy time, programs and gaps."""
import os

import pytest

from chipbench import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "cpu_window.xplane.pb")


def _planes():
    ms = 1_000_000
    host = ("/host:CPU", [
        ("python", [("chipbench.window", 0, 100 * ms),
                    ("chipbench.stage", 0, 10 * ms),
                    ("chipbench.invoke", 10 * ms, 80 * ms),
                    ("unrelated", 0, 100 * ms)]),
    ])
    dev = ("/device:TPU:0", [
        ("XLA Ops", [("fusion.1", 20 * ms, 10 * ms),
                     ("fusion.2", 25 * ms, 10 * ms),     # overlaps
                     ("copy.3", 60 * ms, 5 * ms),
                     ("fusion.1", 95 * ms, 20 * ms)]),   # past the window
        ("XLA Modules", [("jit_prefill(12)", 20 * ms, 15 * ms),
                         ("jit_prefill(12)", 95 * ms, 20 * ms),
                         ("jit_other(3)", 60 * ms, 5 * ms)]),
    ])
    other = ("/device:TPU:0 SparseCore 0", [("XLA Ops", [("x", 0, ms)])])
    return [host, dev, other]


def test_busy_is_the_union_inside_the_window():
    r = tr.reduce_planes(_planes())
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(0.1)
    # [20, 35) + [60, 65) + [95, 100) ms
    assert r["busy_s"] == pytest.approx(0.025)


def test_programs_are_counted_by_name_without_their_id():
    r = tr.reduce_planes(_planes())
    assert r["modules"]["jit_prefill"] == [2, pytest.approx(0.035)]
    assert r["modules"]["jit_other"] == [1, pytest.approx(0.005)]


def test_idle_gaps_go_to_the_innermost_span():
    r = tr.reduce_planes(_planes())
    gaps = dict(r["idle_gaps"])
    # [0, 20): mid 10 ms lies in invoke (stage ends at 10)
    # [35, 60) and [65, 95) lie in invoke; nothing lies outside a span
    assert gaps == {"chipbench.invoke": pytest.approx(0.075)}
    assert sum(gaps.values()) == pytest.approx(0.1 - r["busy_s"])


def test_device_ops_are_summed_by_name():
    ops = dict(tr.reduce_planes(_planes())["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.03)
    assert ops["fusion.2"] == pytest.approx(0.01)


def test_no_device_plane_reads_no_busy_time():
    r = tr.reduce_planes([_planes()[0]])
    assert "busy_s" not in r


def test_recorded_trace_reads_the_window_span():
    """A trace recorded by the JAX profiler (on the CPU: three calls of a
    jitted step inside ``chipbench.invoke`` spans, ``chipbench.stage``
    spans of 10 ms between, all inside ``chipbench.window``)."""
    planes = tr.read_planes(RECORDED)
    host = dict(planes)[tr.HOST_PLANE]
    names = [n for _, events in host for n, _, _ in events]
    assert names.count("chipbench.invoke") == 3
    assert names.count("chipbench.stage") == 3
    r = tr.reduce(RECORDED)
    assert r["chips"] == 0 and "busy_s" not in r
    assert 0.03 < r["window_s"] < 1.0


#: a ``--trace 1`` window of yi-emb-warm recorded on one TPU v5e: four
#: invocations, each one run of ``jit_prefill`` (32, 512)
TPU_RECORDED = os.path.join(HERE, "data", "tpu_emb_window.xplane.pb")


def test_recorded_tpu_trace_reads_the_step_and_busy_time():
    r = tr.reduce(TPU_RECORDED)
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(51.48, abs=0.01)
    runs, secs = r["modules"]["jit_prefill"]
    assert runs == 4 and secs == pytest.approx(4 * 0.4598, rel=1e-3)
    # the step is nearly all the device's work
    assert r["busy_s"] == pytest.approx(secs, rel=0.01)


def test_recorded_tpu_ops_go_by_their_hlo_name():
    r = tr.reduce(TPU_RECORDED)
    names = [n for n, _ in r["device_ops"]]
    assert names[0] == "while"
    assert all(" " not in n and not n.startswith("%") for n in names)
    assert len(names) == tr.TOP
    gaps = dict(r["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)


def test_sound_trace_has_no_fault():
    from chipbench import harness
    assert harness.trace_fault(tr.reduce_planes(_planes()), 1) is None


@pytest.mark.parametrize("keep,chips,match", [
    (lambda p: [p[0]], 1, "no /device:TPU plane"),
    (lambda p: p, 4, "1 device planes for 4 chips"),
    (lambda p: [p[0], (p[1][0], [p[1][1][0]])], 1, "no jit_prefill"),
])
def test_unreadable_trace_is_a_fault(keep, chips, match):
    from chipbench import harness
    fault = harness.trace_fault(tr.reduce_planes(keep(_planes())), chips)
    assert fault and match in fault
