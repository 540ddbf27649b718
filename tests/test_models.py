"""Per-architecture smoke tests (reduced configs) + model invariants.

One test per assigned arch: instantiate the reduced same-family config,
run one train step + prefill + decode on CPU, assert shapes and no
NaNs (the FULL configs are exercised only via the dry-run)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, registry
from repro.configs.base import SHAPES, cell_is_runnable
from repro.models import get_model
from repro.optim import adamw_init, make_train_step


def make_batch(cfg, rng, B=2, S=64):
    if cfg.is_encoder_decoder:
        batch = {
            "src_embeds": jax.random.normal(rng, (B, S, cfg.d_model),
                                            jnp.bfloat16),
            "tgt_tokens": jax.random.randint(rng, (B, S), 0, cfg.vocab_size),
            "targets": jax.random.randint(rng, (B, S), 0, cfg.vocab_size),
        }
        pf = {"src_embeds": batch["src_embeds"],
              "tgt_tokens": batch["tgt_tokens"]}
    elif cfg.embed_input:
        batch = {
            "inputs_embeds": jax.random.normal(rng, (B, S, cfg.d_model),
                                               jnp.bfloat16),
            "targets": jax.random.randint(rng, (B, S), 0, cfg.vocab_size),
        }
        pf = {"inputs_embeds": batch["inputs_embeds"]}
    else:
        toks = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)
        batch = {"tokens": toks, "targets": toks}
        pf = {"tokens": toks}
    return batch, pf


@pytest.mark.parametrize("arch", ARCH_IDS)
class TestArchSmoke:
    def test_forward_prefill_decode(self, arch):
        cfg = registry.get_smoke(arch)
        model = get_model(cfg)
        rng = jax.random.PRNGKey(0)
        params = model.init_params(rng)
        B, S = 2, 64
        batch, pf = make_batch(cfg, rng, B, S)

        loss, metrics = jax.jit(model.loss)(params, batch)
        assert loss.shape == ()
        assert jnp.isfinite(loss), f"{arch}: loss {loss}"

        logits, cache = jax.jit(model.prefill)(params, pf)
        assert logits.shape == (B, 1, cfg.vocab_size)
        assert bool(jnp.all(jnp.isfinite(logits)))

        tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
        logits2, cache2 = jax.jit(model.decode_step)(params, cache, tok)
        assert logits2.shape == (B, 1, cfg.vocab_size)
        assert bool(jnp.all(jnp.isfinite(logits2)))
        assert int(cache2["pos"][0]) == S + 1

    def test_train_step_updates(self, arch):
        cfg = registry.get_smoke(arch)
        model = get_model(cfg)
        rng = jax.random.PRNGKey(1)
        params = model.init_params(rng)
        state = adamw_init(params)
        batch, _ = make_batch(cfg, rng)
        step = jax.jit(make_train_step(model))
        new_state, metrics = step(state, batch)
        assert jnp.isfinite(metrics["loss"])
        assert int(new_state.step) == 1
        # at least one param leaf actually moved
        moved = any(
            bool(jnp.any(a != b))
            for a, b in zip(jax.tree.leaves(state.params),
                            jax.tree.leaves(new_state.params)))
        assert moved


class TestDecodeConsistency:
    """Prefill-then-decode must match teacher-forced full-sequence runs."""

    @pytest.mark.parametrize("arch", ["llama3-8b", "falcon-mamba-7b",
                                      "hymba-1.5b", "mixtral-8x22b"])
    def test_decode_matches_prefill_logits(self, arch):
        cfg = registry.get_smoke(arch).replace(remat_policy="none")
        model = get_model(cfg)
        rng = jax.random.PRNGKey(2)
        params = model.init_params(rng)
        B, S = 1, 32
        toks = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)

        # full prefill on S tokens -> last-token logits
        logits_full, _ = model.prefill(params, {"tokens": toks})
        # prefill on S-1, then decode token S-1
        logits_pre, cache = model.prefill(params, {"tokens": toks[:, :-1]},
                                          cache_len=S)
        logits_dec, _ = model.decode_step(params, cache, toks[:, -1:])
        np.testing.assert_allclose(
            np.asarray(logits_full[:, -1], np.float32),
            np.asarray(logits_dec[:, 0], np.float32),
            atol=0.3, rtol=0.05)     # bf16 params, different compute paths


class TestMoE:
    def test_sorted_matches_dense_oracle(self):
        from repro.models import moe as MOE
        cfg = registry.get_smoke("mixtral-8x22b")
        rng = jax.random.PRNGKey(3)
        p = MOE.init_moe(rng, cfg, jnp.float32)
        x = jax.random.normal(rng, (2, 16, cfg.d_model), jnp.float32)
        y_sorted, aux_s, tokens = MOE.moe_layer(p, cfg, x)
        y_dense, aux_d = MOE.moe_dense(p, cfg, x)
        # float32 both ways; only the order of the sums differs
        np.testing.assert_allclose(np.asarray(y_sorted),
                                   np.asarray(y_dense), atol=1e-4,
                                   rtol=1e-3)
        np.testing.assert_allclose(float(aux_s), float(aux_d), rtol=1e-5)
        assert int(tokens.sum()) == 2 * 16 * cfg.num_experts_per_tok

    def test_capacity_drops_are_bounded(self):
        """There is no capacity: under uneven routing, where a 1.25x
        capacity buffer would overflow, every assignment is computed."""
        from repro.models import moe as MOE
        cfg = registry.get_smoke("qwen3-moe-30b-a3b")
        rng, skew = jax.random.split(jax.random.PRNGKey(4))
        p = MOE.init_moe(rng, cfg, jnp.float32)
        # one direction shared by every token skews the routing
        x = (jax.random.normal(rng, (2, 64, cfg.d_model), jnp.float32)
             + 2.0 * jax.random.normal(skew, (cfg.d_model,), jnp.float32))
        y, aux, tokens = MOE.moe_layer(p, cfg, x)
        y_dense, _ = MOE.moe_dense(p, cfg, x)
        assert bool(jnp.all(jnp.isfinite(y)))
        assert float(aux) >= 0.0
        A = 2 * 64 * cfg.num_experts_per_tok
        assert int(tokens.max()) > 1.25 * A / cfg.num_experts
        assert int(tokens.sum()) == A
        # float32 both ways; only the order of the sums differs
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_dense),
                                   atol=1e-4, rtol=1e-3)


class TestLongContext:
    def test_swa_ring_cache_is_window_bounded(self):
        """long_500k viability: cache width never exceeds the window."""
        cfg = registry.get_smoke("mixtral-8x22b")
        from repro.models import kv_cache as kvc
        cache = kvc.init_cache(cfg, batch=1, seq_len=8192)
        assert cache["k"].shape[2] == cfg.sliding_window
        assert cell_is_runnable(registry.get("mixtral-8x22b"),
                                SHAPES["long_500k"])[0]

    def test_full_attn_long_context_skipped(self):
        ok, why = cell_is_runnable(registry.get("llama3-8b"),
                                   SHAPES["long_500k"])
        assert not ok and "full-attn" in why

    def test_ssm_decode_state_is_o1(self):
        cfg = registry.get_smoke("falcon-mamba-7b")
        from repro.models import kv_cache as kvc
        c1 = kvc.init_cache(cfg, batch=1, seq_len=1024)
        c2 = kvc.init_cache(cfg, batch=1, seq_len=1 << 19)
        assert c1["ssm"].shape == c2["ssm"].shape      # O(1) in context


class TestParamAccounting:
    def test_published_param_counts(self):
        """Analytic param counts land near the published model sizes."""
        expect = {"llama3-8b": 8.0e9, "qwen2-72b": 72.7e9,
                  "yi-34b": 34.4e9, "mixtral-8x22b": 141e9,
                  "falcon-mamba-7b": 7.3e9}
        for arch, n in expect.items():
            got = registry.get(arch).param_count()
            assert abs(got - n) / n < 0.12, f"{arch}: {got:.3g} vs {n:.3g}"

    def test_moe_active_params(self):
        cfg = registry.get("mixtral-8x22b")
        total, active = cfg.param_count(), cfg.active_param_count()
        assert active < total * 0.45          # top-2 of 8 experts + shared
        assert active > total * 0.15
