"""The qwen3_moe architecture's counts, its expert-layer metric and its
float8 control."""
import json
import os
import time

import pytest

from chipbench import architectures, flops, harness
from chipbench.reference import weights
from chipbench.tests.conftest import FAKE_TPU, config_spec, smoke_cell

CONFIG = "qwen3-30b-a3b-24l"
CELL = "qwen3-emb-warm"


def test_counts_of_the_chips_share():
    """94,638,336 parameters a layer, 24 layers plus the embedding, norm
    and untied head; the step's operations count the router's 128
    outputs and, per token, 8 x 16/128 = 1 held expert."""
    spec = config_spec(CONFIG)
    assert weights.param_count(spec) == 2_893_651_968
    assert weights.nbytes(spec) == 5_787_303_936
    per_layer = (weights.param_count(spec)
                 - 2 * 151_936 * 2048 - 2048) // 24
    assert per_layer == 94_638_336
    f = flops.prefill_flops(spec, 32, 512)
    assert f["total"] == pytest.approx(2.043e13, rel=1e-3)
    cfg = harness.program_config(spec)
    assert (cfg.num_experts, cfg.num_experts_per_tok) == (128, 8)
    assert cfg.expert_share == (0, 16)


def test_program_without_a_held_share_is_refused(monkeypatch):
    """A program whose expert layer holds every expert cannot serve the
    configuration: the run exits 2 before it touches the chip."""
    import dataclasses
    from repro.configs import base

    @dataclasses.dataclass(frozen=True)
    class Unshared:
        name: str

    monkeypatch.setattr(base, "ModelConfig", Unshared)
    arch = architectures.find("qwen3_moe")
    with pytest.raises(harness.Refused) as e:
        arch.arch.program_config(config_spec(CONFIG))
    assert e.value.code == 2


def _step_span(M, **attrs):
    with M.span("nexus.handler.step", **attrs):
        pass


def test_expert_imbalance_reads_the_step_spans():
    """Two invocations: one with an even load (each of 16 held experts
    computed 100 of 1600 assignments a layer), one whose busiest expert
    took three times its share; spans of another name, and step spans
    without counters, count nothing."""
    from repro.core import metrics as M
    t0 = time.monotonic()
    time.sleep(0.001)
    _step_span(M, expert_tokens=24 * 1600, expert_tokens_max=24 * 100,
               experts=16)
    _step_span(M, expert_tokens=24 * 1600, expert_tokens_max=24 * 300,
               experts=16)
    _step_span(M)
    with M.span("nexus.handler.encode", expert_tokens=1):
        pass
    run = {"invocations": [{"t_submit": t0}]}
    assert harness._reader("expert_imbalance.warm")(run) == pytest.approx(2.0)


def test_expert_imbalance_is_silent_without_counters():
    from repro.core import metrics as M
    t0 = time.monotonic()
    time.sleep(0.001)
    _step_span(M)
    assert harness._reader("expert_imbalance.warm")({"invocations": [
        {"t_submit": t0}]}) is None


def test_smoke_run_reports_the_expert_imbalance():
    cell = smoke_cell(CELL)
    cell.metrics = [dict(m, trace=0) for m in cell.metrics
                    if m["name"] == "expert_imbalance.warm"]
    res = harness.run_cell(cell, 2**31 + 21, 1.0, False, 0.0, FAKE_TPU,
                           log=lambda *_: None)
    value = res["metrics"]["expert_imbalance.warm"]["value"]
    assert 1.0 <= value < 16.0


def test_float8_control_fails_the_limit_through_the_check():
    """`limits.py`'s path: the check recomputes a SMOKE window's answers
    with the reference in float32 and in float8; the program's numbers
    keep to the cell's limit, the control's do not."""
    cell = smoke_cell(CELL)
    cell.mix = dict(cell.mix, check_sample=3)
    seed = 2**31 + 23
    run = harness.serve(cell, seed, 1.0, False, 0.0, log=lambda *_: None)
    res = harness.check(cell, seed, run, control=True)
    limit = cell.limits["logits_rel_err"]["limit"]
    assert res["program"]["logits_rel_err"] <= limit, res
    assert res["control"]["logits_rel_err"] > limit, res


def test_configuration_states_its_cut():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        conf = {c["name"]: c for c in json.load(f)["configs"]}[CONFIG]
    assert conf["reduced"] == ["num_hidden_layers", "num_experts"]
    spec = config_spec(CONFIG)
    assert spec["published"] == {"num_hidden_layers": 48,
                                 "num_experts": 128}
    assert "16" in spec["deployment"] and "experts 0-15" in spec["deployment"]
