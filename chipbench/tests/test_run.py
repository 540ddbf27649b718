"""A whole run of each cell at SMOKE size on the CPU, sound and broken.

The harness's look for a chip is skipped (the device is given); all the
rest runs: weights, staging, the node, the closed-loop window, the read
back and the check. A fault planted in the handler core, where the
answer is produced, has to turn ``correct`` false.
"""
import numpy as np
import pytest

from chipbench import harness
from chipbench.tests.conftest import FAKE_TPU

WINDOW_S = 1.0


def _run(cell, seed=2**31 + 11):
    return harness.run_cell(cell, seed, WINDOW_S, False, 0.0, FAKE_TPU,
                            log=lambda *_: None)


def test_sound_run_is_correct(cell):
    res = _run(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    e2e = {m["name"] for m in cell.metrics if m["trace"] == 0}
    assert set(res["metrics"]) == e2e
    assert all(m["value"] > 0 for m in res["metrics"].values())


def test_every_invocation_hits_the_weights_in_the_cache(cell):
    run = harness.serve(cell, 2**31 + 12, WINDOW_S, False, 0.0,
                        log=lambda *_: None)
    n = len(run["invocations"]) + 1             # the warm-up's too
    assert run["cache"]["admitted"] >= 1
    assert run["cache"]["hits"] >= n, run["cache"]


def _altered(out):
    """One answer altered where it is produced: the first and last
    positions (or rows) swapped."""
    if isinstance(out, dict):
        return dict(out, k=out["k"][:, :, ::-1], v=out["v"][:, :, ::-1])
    return out[::-1]


def _half_batch(out):
    """Half of the batch (or of the positions) left out: the second half
    a copy of the first."""
    def half(a, axis):
        a = np.array(a)
        n = a.shape[axis] // 2
        idx = [slice(None)] * a.ndim
        idx[axis] = slice(n, 2 * n)
        src = [slice(None)] * a.ndim
        src[axis] = slice(0, n)
        a[tuple(idx)] = a[tuple(src)]
        return a
    if isinstance(out, dict):
        return dict(out, k=half(out["k"], 2), v=half(out["v"], 2))
    return half(out, 0)


@pytest.mark.parametrize("fault", [_altered, _half_batch])
def test_broken_path_is_not_correct(cell, fault, monkeypatch):
    from repro.models import serialize, serving
    core = {"EMB": "emb_encode", "LLM-PREFILL": "llm_prefill"}[
        cell.mix["scenario"]]
    real = getattr(serving, core)
    struct = cell.mix["output"]

    def broken(*args, **kw):
        b = serving.bundle(args[-1])
        out = serialize.loads(b["structs"][struct], real(*args, **kw))
        return serialize.dumps(fault(out))

    monkeypatch.setattr(serving, core, broken)
    res = _run(cell)
    assert not res["correct"]


def test_scale_to_zero_invocation_that_stays_warm_fails(monkeypatch):
    from repro.core.lifecycle import InstancePool
    from chipbench.tests.conftest import smoke_cell
    monkeypatch.setattr(InstancePool, "scale_down", lambda *a, **k: None)
    res = _run(smoke_cell("yi-prefill-coldvm"))
    assert res["failed"] >= 1
    assert not res["correct"]
