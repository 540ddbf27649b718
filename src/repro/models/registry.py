"""Family dispatch: one uniform Model facade over the family modules."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import encdec, lm
from repro.models import kv_cache as kvc


class Model:
    """Uniform interface: init_params / loss / prefill / decode_step."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self._mod = encdec if cfg.is_encoder_decoder else lm

    def init_params(self, rng):
        return self._mod.init_params(rng, self.cfg)

    def loss(self, params, batch):
        return self._mod.forward_train(self.cfg, params, batch)

    def prefill(self, params, batch, cache_len=None, counters=False):
        return self._mod.prefill(self.cfg, params, batch,
                                 cache_len=cache_len, counters=counters)

    def decode_step(self, params, cache, token):
        return self._mod.decode_step(self.cfg, params, cache, token)

    def init_cache(self, batch, seq_len, dtype=jnp.bfloat16):
        return kvc.init_cache(self.cfg, batch, seq_len, dtype=dtype)

    def param_shapes(self, rng=None):
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        return jax.eval_shape(lambda r: self.init_params(r), rng)


def get_model(cfg: ModelConfig) -> Model:
    return Model(cfg)


def param_count(cfg: ModelConfig, active_only: bool = False) -> int:
    """Parameter count from abstract shapes (no allocation).

    active_only: count only the expert FFN params a token is expected
    to touch, top-k of the router's experts, of which the layer holds
    its share (for MODEL_FLOPS = 6 * N_active * D rooflines).
    """
    import math

    model = Model(cfg)
    shapes = model.param_shapes()
    total = sum(math.prod(l.shape) for l in jax.tree.leaves(shapes))
    if active_only and cfg.num_experts:
        # the held experts' FFN leaves scale by k/E: a token meets
        # k * held/E of them on average
        def expert_bytes(tree):
            layers = tree["layers"]["moe"]
            return sum(math.prod(layers[k].shape)
                       for k in ("w_gate", "w_up", "w_down"))
        e_total = expert_bytes(shapes)
        total = total - e_total + e_total * cfg.num_experts_per_tok // cfg.num_experts
    return total
