"""An expert layer that holds a share of the router's experts.

The layer routes every token over all ``num_experts`` experts, top-k
with the gates renormalised, and computes, dropless, only its held
experts' part of the result (`repro.models.moe`). The registry's MoE
configs hold all their experts, so their params, calibration and the
MOE scenario's sizes are those of the layer before it could hold a
share.
"""
import hashlib
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.models import get_model
from repro.models import moe as MOE

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(held=0, first=0, **kw):
    """qwen3's SMOKE layer with a router of 16, top-4: enough experts to
    cut into eight shares of two."""
    return registry.get_smoke("qwen3-moe-30b-a3b").replace(
        num_experts=16, num_experts_per_tok=4, held_experts=held,
        first_expert=first, **kw)


def _layer(cfg, seed=0):
    return MOE.init_moe(jax.random.PRNGKey(seed), cfg, jnp.float32)


def _x(cfg, shape=(2, 32), seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (*shape, cfg.d_model), jnp.float32)


def _share(p, first, n):
    return dict(p, **{k: p[k][first:first + n]
                      for k in ("w_gate", "w_up", "w_down")})


@pytest.mark.parametrize("first,held", [(0, 16), (0, 2), (6, 4), (15, 1)])
def test_held_share_matches_dense_oracle(first, held):
    cfg = _cfg(held, first)
    p, x = _layer(cfg), _x(cfg)
    y, aux, tokens = jax.jit(lambda p, x: MOE.moe_layer(p, cfg, x))(p, x)
    y_dense, aux_dense = MOE.moe_dense(p, cfg, x)
    # float32 both ways; only the order of the sums differs
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_dense),
                               atol=1e-5, rtol=1e-4)
    assert float(aux) == pytest.approx(float(aux_dense), rel=1e-6)
    # the counts are the held experts' columns of the routing
    _, idx, _ = MOE._route(p, cfg, x.reshape(-1, cfg.d_model))
    want = np.bincount(np.asarray(idx).ravel(), minlength=16)
    np.testing.assert_array_equal(np.asarray(tokens),
                                  want[first:first + held])


def test_one_held_expert_takes_every_token_and_drops_none():
    """A router that sends every token to expert 5 first: its group is
    all T tokens, 16/4 = 4x what a 1.25x capacity buffer would hold."""
    cfg = _cfg(held=4, first=4)
    p = _layer(cfg)
    x = _x(cfg).at[..., 0].set(8.0)
    p["router"] = p["router"].at[0].set(0.0).at[0, 5].set(4.0)
    T = x.shape[0] * x.shape[1]
    y, _, tokens = MOE.moe_layer(p, cfg, x)
    assert int(tokens[5 - 4]) == T
    _, idx, _ = MOE._route(p, cfg, x.reshape(T, -1))
    assert bool(jnp.all(idx[:, 0] == 5))
    y_dense, _ = MOE.moe_dense(p, cfg, x)
    # float32 both ways; only the order of the sums differs
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_dense),
                               atol=1e-5, rtol=1e-4)


def test_shares_add_up_to_the_uncut_layer():
    """Eight chips of two experts each: the parts of the result their
    layers give add up to the whole layer's (nothing is computed alike
    on every chip: no shared expert)."""
    whole = _cfg()
    p, x = _layer(whole), _x(whole)
    y_whole, _ = MOE.moe_dense(p, whole, x)
    parts = [MOE.moe_layer(_share(p, 2 * c, 2), _cfg(2, 2 * c), x)[0]
             for c in range(8)]
    # float32; eight partial sums in another order than one
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(y_whole),
                               atol=1e-5, rtol=1e-4)


def test_share_outside_the_router_is_refused():
    with pytest.raises(ValueError, match="not among the router's 16"):
        _cfg(held=4, first=14)
    with pytest.raises(ValueError):
        _cfg(held=-1)


def test_active_params_count_the_held_share():
    """A token meets k x held/E of the held experts on average: the
    compute bound that `ml_suite_at` derives counts that share."""
    whole, share = _cfg(), _cfg(held=4, first=8)
    expert = 3 * whole.d_model * whole.d_ff * whole.num_layers
    assert whole.param_count() - share.param_count() == 12 * expert
    assert (whole.active_param_count() - share.active_param_count()
            == 12 * expert * 4 // 16)


def _served_spec():
    """The benchmark's qwen3 configuration at a small size, in float32."""
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "qwen3-30b-a3b-24l.json")) as f:
        spec = json.load(f)
    return dict(spec, name=spec["name"] + "-smoke", torch_dtype="float32",
                hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
                head_dim=16, moe_intermediate_size=96, vocab_size=512,
                num_hidden_layers=3, num_experts=4, first_expert=2,
                num_experts_per_tok=4,
                published=dict(spec["published"], num_experts=16))


def test_prefill_then_decode_matches_the_reference():
    """Prefill S-1 tokens into a cache wider than S (clear of the ring
    slot that wraps at S), decode token S: the logits are the plain
    reference's at the last position of a full forward of S tokens."""
    from chipbench import architectures, harness
    from chipbench.reference import weights
    spec = _served_spec()
    arch = architectures.find("qwen3_moe")
    cfg = harness.program_config(spec, arch)
    model = get_model(cfg)
    params = harness.program_tree(weights.make(spec, 7, arch),
                                  model.param_shapes())
    S = 12
    tokens = np.random.default_rng(7).integers(0, 512, (2, S), np.int32)
    _, cache = jax.jit(model.prefill, static_argnums=2)(
        params, {"tokens": tokens[:, :-1]}, S + 4)
    logits, _ = jax.jit(model.decode_step)(params, cache, tokens[:, -1:])
    ref = arch.reference.forward(spec, weights.make(spec, 7, arch), tokens)
    # float32 both ways: the decode path (masked-dense experts, one
    # token against the cache) differs from the reference only in the
    # order of its sums
    np.testing.assert_allclose(np.asarray(logits), ref["logits"],
                               atol=1e-5, rtol=1e-4)


def test_served_step_counts_the_held_experts_tokens():
    """The served step returns each layer's held group sizes with its
    outputs, and the handler puts their sums on its step span."""
    from repro.core import metrics as M
    from repro.models import serialize, serving
    cfg = _cfg(held=4, first=4)
    b = serving.bundle(cfg)
    params = b["model"].init_params(jax.random.PRNGKey(0))
    tokens = serving._prompt_tokens(b, "enc_tokens")
    _, _, counters = b["prefill"](params, {"tokens": tokens})
    got = np.asarray(counters["expert_tokens"])
    assert got.shape == (cfg.num_layers, 4)
    assert got.sum() <= tokens.size * cfg.num_experts_per_tok
    t0 = time.monotonic_ns()
    serving.emb_encode(serialize.dumps(params), serialize.dumps(tokens), cfg)
    step = [s for s in M.SPANS.since(t0) if s.name == "nexus.handler.step"]
    assert step[-1].attrs["expert_tokens"] == int(got.sum())
    assert step[-1].attrs["expert_tokens_max"] == int(got.max(-1).sum())
    assert step[-1].attrs["experts"] == 4


#: sha256 of each registry MoE config's params tree (leaf path, shape,
#: dtype), param counts and the MOE scenario's GET/PUT sizes, recorded
#: with the capacity-buffer layer that held every expert
REGISTRY_TREES = {
    "qwen3-moe-30b-a3b":
        "9728ade224bb5039a70ea192b312317f4a768b18832847c75c7e984cf8e8360b",
    "qwen3-moe-30b-a3b-smoke":
        "e7ea90373642328db47512287d4ea8af6d426153eb54bb1ec0534c9e1fbc0e45",
    "mixtral-8x22b":
        "fca976168ca6c5e77b36d7ef352a8c3307a860185e56a164f90721b947875862",
    "mixtral-8x22b-smoke":
        "2c50e6c84a77bdf3c07b7be5c015132dce9a20e25fa8b7a9acffeb701399fe0d",
}
REGISTRY_COUNTS = {
    "qwen3-moe-30b-a3b": (30532122624, 3353032704),
    "qwen3-moe-30b-a3b-smoke": (386432, 165248),
    "mixtral-8x22b": (140630071296, 39161468928),
    "mixtral-8x22b-smoke": (1017472, 624256),
}
MOE_SIZES = {"full": [20363137024] * 3 + [607744],
             "smoke": [258304] * 3 + [2048]}


@pytest.mark.parametrize("arch", ["qwen3-moe-30b-a3b", "mixtral-8x22b"])
def test_registry_moe_params_are_unchanged(arch):
    for cfg in (registry.get(arch), registry.get_smoke(arch)):
        tree = get_model(cfg).param_shapes()
        leaves = sorted((jax.tree_util.keystr(p), tuple(leaf.shape),
                         str(leaf.dtype))
                        for p, leaf in jax.tree_util.tree_leaves_with_path(tree))
        digest = hashlib.sha256(json.dumps(leaves).encode()).hexdigest()
        assert digest == REGISTRY_TREES[cfg.name]
        assert (cfg.param_count(), cfg.active_param_count()) == \
            REGISTRY_COUNTS[cfg.name]
        assert tree["layers"]["moe"]["router"].dtype == jnp.float32


def test_moe_calibration_and_scenario_sizes_are_unchanged():
    from repro.core import calibrate
    from repro.core.workloads import ml_suite_at
    cal = calibrate.load_calibration()
    for scale in calibrate.SCALES:
        assert calibrate._derive_role(scale, "moe") == \
            cal["models"][f"{scale}/moe"]
    for size, cfg in (("full", registry.get("qwen3-moe-30b-a3b")),
                      ("smoke", registry.get_smoke("qwen3-moe-30b-a3b"))):
        w = ml_suite_at({"moe": cfg})["MOE"]
        assert [op.size_bytes for op in (*w.profile.gets,
                                         *w.profile.puts)] == MOE_SIZES[size]
