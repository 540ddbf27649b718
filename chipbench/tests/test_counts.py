"""Operation and parameter counts of the cells' configurations."""
import json

import jax
import pytest

from chipbench import flops, harness
from chipbench.reference import weights

#: parameters of each configuration as served, and its bf16 bytes
PARAMS = {"yi-34b-4l": 3_148_938_240}
#: (configuration, (batch, sequence)) of each cell's step
CELL_SHAPES = [("yi-34b-4l", (32, 512)), ("yi-34b-4l", (1, 2048))]


def _spec(config):
    with open(f"{harness.ROOT}/chipbench/configs/{config}.json") as f:
        return json.load(f)


@pytest.mark.parametrize("config", sorted(PARAMS))
def test_parameter_counts(config):
    from repro.models import get_model
    spec = _spec(config)
    model = get_model(harness.program_config(spec))
    struct = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    n = sum(leaf.size for leaf in jax.tree.leaves(struct))
    assert n == PARAMS[config] == weights.param_count(spec)
    assert weights.nbytes(spec) == 2 * PARAMS[config]


@pytest.mark.parametrize("config,shape", CELL_SHAPES)
def test_flops_agree_with_the_programs_count(config, shape):
    from repro.configs.base import InputShape
    from repro.models.flops import model_flops
    spec = _spec(config)
    cfg = harness.program_config(spec)
    B, S = shape
    ours = flops.prefill_flops(spec, B, S)
    theirs = model_flops(cfg, InputShape("cell", S, B, "prefill"))
    assert ours["attention"] == theirs["attention"]
    # the program's count applies every parameter, embedding, head and
    # norms too, to every position; the benchmark's counts products
    D, V, L = spec["hidden_size"], spec["vocab_size"], cfg.num_layers
    not_products = (V * D * (1 if cfg.tie_embeddings else 2)
                    + (2 * L + 1) * D)
    assert ours["layers"] == theirs["core"] - 2.0 * not_products * B * S
    assert ours["head"] == 2.0 * B * D * V


def test_cell_step_flops():
    emb = flops.prefill_flops(_spec("yi-34b-4l"), 32, 512)["total"]
    prefill = flops.prefill_flops(_spec("yi-34b-4l"), 1, 2048)["total"]
    assert 73.62e12 < emb < 73.63e12
    assert 9.38e12 < prefill < 9.39e12
