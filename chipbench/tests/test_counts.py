"""Operation and parameter counts of the cells' configurations."""
import jax
import pytest

from chipbench import flops, harness
from chipbench.reference import weights
from chipbench.tests.conftest import CELLS, CONFIGS, config_spec

#: (configuration, (batch, sequence)) of each cell's step whose
#: configuration is a llama, the architecture the program's own
#: `models/flops.py` counts
CELL_SHAPES = [(c.spec["name"], (c.mix["batch"], c.mix["seq"]))
               for c in map(harness.load_cell, CELLS)
               if c.spec["reference"] == "llama"]


@pytest.mark.parametrize("config", CONFIGS)
def test_parameter_counts(config):
    from repro.models import get_model
    spec = config_spec(config)
    model = get_model(harness.program_config(spec))
    struct = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    n = sum(leaf.size for leaf in jax.tree.leaves(struct))
    assert n == weights.param_count(spec)
    item = jax.numpy.dtype(spec["torch_dtype"]).itemsize
    assert weights.nbytes(spec) == item * n


@pytest.mark.parametrize("config,shape", CELL_SHAPES)
def test_flops_agree_with_the_programs_count(config, shape):
    from repro.configs.base import InputShape
    from repro.models.flops import model_flops
    spec = config_spec(config)
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
    emb = flops.prefill_flops(config_spec("yi-34b-4l"), 32, 512)["total"]
    prefill = flops.prefill_flops(config_spec("yi-34b-4l"), 1, 2048)["total"]
    assert 73.62e12 < emb < 73.63e12
    assert 9.38e12 < prefill < 9.39e12
