"""Each architecture's plain reference against the program's own prefill,
at SMOKE size.

In float32 the two are the same mathematics and agree to rounding; in
the bfloat16 the configurations serve, the program stays inside each
cell's limit, and the float8 control does not.
"""
import jax
import numpy as np
import pytest

from chipbench import architectures, harness
from chipbench.compare import rel_err
from chipbench.reference import weights
from chipbench.tests.conftest import (CELLS, CONFIGS, config_spec,
                                      smoke_cell, smoke_spec)

SEEDS = (3, 2**31 + 5)


def _program(spec, seed, tokens):
    from repro.models import get_model
    cfg = harness.program_config(spec)
    model = get_model(cfg)
    struct = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    params = harness.program_tree(weights.make(spec, seed), struct)
    logits, cache = jax.jit(model.prefill)(params, {"tokens": tokens})
    return jax.tree.map(np.asarray, (logits, cache))


def _tokens(spec, shape, seed):
    return np.random.default_rng(seed).integers(
        0, spec["vocab_size"], shape, dtype=np.int32)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("config", CONFIGS)
def test_float32_program_matches_reference(config, seed):
    spec = dict(smoke_spec(config_spec(config)), torch_dtype="float32")
    tokens = _tokens(spec, (2, 24), seed)
    logits, cache = _program(spec, seed, tokens)
    ref = architectures.of(spec).reference.forward(
        spec, weights.make(spec, seed), tokens, want=("logits", "kv"),
        row_block=1)
    assert rel_err(logits, ref["logits"]) < 1e-4
    for name in ("k", "v"):
        assert rel_err(cache[name], ref[name]) < 1e-4


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", CELLS)
def test_served_precision_inside_limit_and_control_outside(name, seed):
    cell = smoke_cell(name)
    spec, mix = cell.spec, cell.mix
    tokens = _tokens(spec, (mix["batch"], mix["seq"]), seed)
    logits, cache = _program(spec, seed, tokens)
    w = weights.make(spec, seed)
    arch = architectures.of(spec)
    want = (mix["compare"],)
    ref = arch.reference.forward(spec, w, tokens, want=want)
    low = arch.reference.forward(spec, w, tokens, want=want,
                                 precision="float8_e4m3fn")
    numbers = arch.numbers(mix["compare"])
    if mix["compare"] == "kv":
        served = numbers([(cache, ref)])
        ctl = dict(low, pos=cache["pos"], slot_pos=cache["slot_pos"])
        control = numbers([(ctl, ref)])
    else:
        served = numbers([(logits, ref["logits"])])
        control = numbers([(low["logits"], ref["logits"])])
    limits = {n: v["limit"] for n, v in cell.limits.items()}
    assert all(served[n] <= limits[n] for n in served), served
    assert any(control[n] > limits[n] for n in control), control
