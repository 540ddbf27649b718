"""A run refuses to measure anywhere but on a chip it has peaks for."""
import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from chipbench import harness
from chipbench.reference import weights
from chipbench.tests.conftest import CELLS, smoke_cell


def test_cpu_device_is_refused():
    with pytest.raises(harness.Refused) as e:
        harness.check_device(1, {"devices": {"TPU v5 lite": {}}})
    assert e.value.code != 0


def test_device_kind_without_peaks_is_refused(monkeypatch):
    fake = SimpleNamespace(platform="tpu", device_kind="TPU v99")
    monkeypatch.setattr(jax, "devices", lambda: [fake])
    peaks = harness.load_cell("yi-emb-warm").peaks
    with pytest.raises(harness.Refused, match="TPU v99"):
        harness.check_device(1, peaks)


def test_too_few_chips_are_refused(monkeypatch):
    fake = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda: [fake])
    with pytest.raises(harness.Refused, match="4 chips"):
        harness.check_device(4, {"devices": {"TPU v5 lite": {}}})


def test_main_on_cpu_exits_nonzero_without_a_result(capsys):
    rc = harness.main(["--workload", "yi-emb-warm", "--seed", "1",
                       "--seconds", "1", "--trace", "0"], 0.0)
    out = capsys.readouterr()
    assert rc != 0
    assert "correct" not in out.out


def test_benchmark_alone_exits_nonzero_without_a_result(tmp_path):
    """A directory that holds only BENCHMARK.json and ``chipbench/``."""
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(harness.ROOT, "chipbench"),
                    tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "yi-emb-warm",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_benchmark_file_names_what_the_harness_finds():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    here = os.path.join(harness.ROOT, "chipbench")
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert os.path.exists(os.path.join(here, "metrics",
                                           f"{m['name']}.py")), m["name"]
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        names = {m["name"] for m in cell.metrics}
        assert "setup_s" in names
        assert any(m["trace"] == 0 and m["name"] != "setup_s"
                   for m in cell.metrics)
        assert any(m["trace"] == 1 for m in cell.metrics)
        for m in cell.metrics:
            if m["trace"] == 1:
                assert m["moves"] in names, (w["name"], m["name"])


@pytest.mark.parametrize("name", CELLS)
def test_limits_name_every_compared_number(name):
    """The cell's limits are those of the numbers its check reads: the
    reference's answer, in the form the program serves it, against
    itself."""
    cell = smoke_cell(name)
    spec, mix = cell.spec, cell.mix
    tokens = np.zeros((mix["batch"], mix["seq"]), np.int32)
    ref = cell.arch.reference.forward(spec, weights.make(spec, 1, cell.arch),
                                      tokens, want=(mix["compare"],))
    got = harness._as_served(ref, tokens)
    numbers = cell.arch.numbers(mix["compare"])(
        [harness._pair(mix, got, ref)])
    assert set(numbers) == set(cell.limits)
    assert all(v == 0 for v in numbers.values()), numbers
