"""Seeded weights of a llama-architecture configuration, made on the device.

The benchmark, not the system under test, makes the weights: the harness
uploads them (the client's checkpoint) and the plain reference makes the
same values again from the same seed, so the reference takes nothing the
program made.

Leaves are named by the checkpoint layout the served program reads
(``layers.attn.wq`` is the stacked (L, D, H*hd) query projection), one
name per array. Every value is uniform, drawn from 16 random bits per
element, with the spread of a LeCun initialisation for a matrix and
1 +- 0.2 for a norm scale. The bits come from threefry, an integer
computation, so a value depends only on the seed and the leaf.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

#: a norm scale is 1 + NORM_SPREAD * u with u uniform in (-1, 1)
NORM_SPREAD = 0.2


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


def shapes(spec: dict) -> dict[str, tuple[int, ...]]:
    """Leaf name -> shape, in sorted name order."""
    L = spec["num_hidden_layers"]
    D = spec["hidden_size"]
    H, K = spec["num_attention_heads"], spec["num_key_value_heads"]
    hd, F, V = spec["head_dim"], spec["intermediate_size"], spec["vocab_size"]
    out = {
        "embed": (V, D),
        "final_norm": (D,),
        "layers.attn.wk": (L, D, K * hd),
        "layers.attn.wo": (L, H * hd, D),
        "layers.attn.wq": (L, D, H * hd),
        "layers.attn.wv": (L, D, K * hd),
        "layers.ln1": (L, D),
        "layers.ln2": (L, D),
        "layers.mlp.w_down": (L, F, D),
        "layers.mlp.w_gate": (L, D, F),
        "layers.mlp.w_up": (L, D, F),
    }
    if not spec["tie_word_embeddings"]:
        out["lm_head"] = (D, V)
    return dict(sorted(out.items()))


def _fan_in(name: str, shape: tuple[int, ...]) -> int:
    # the embedding table's rows are vectors of width D, like a matrix's
    # input side: both are scaled by 1/sqrt(D)
    return shape[-1] if name == "embed" else shape[-2]


def _leaf(key, name: str, shape: tuple[int, ...], dtype):
    bits = jax.random.bits(key, shape, jnp.uint16)
    u = (bits.astype(jnp.float32) + 0.5) * (2.0 / 65536.0) - 1.0
    if len(shape) == 1 or name.endswith(("ln1", "ln2")):
        return (1.0 + NORM_SPREAD * u).astype(dtype)
    return (u * (math.sqrt(3.0) / math.sqrt(_fan_in(name, shape)))
            ).astype(dtype)


@functools.lru_cache(maxsize=None)
def _maker(items: tuple, dtype: str):
    def make(key):
        return {n: _leaf(jax.random.fold_in(key, i), n, s, jnp.dtype(dtype))
                for i, (n, s) in enumerate(items)}
    return jax.jit(make)


def make(spec: dict, seed: int) -> dict:
    """Every leaf of `spec`'s weights from `seed`, in one jitted call, in
    the dtype the configuration serves (``torch_dtype``)."""
    items = tuple(shapes(spec).items())
    return _maker(items, spec["torch_dtype"])(seed_key(seed))


def nbytes(spec: dict) -> int:
    item = jnp.dtype(spec["torch_dtype"]).itemsize
    return sum(math.prod(s) for s in shapes(spec).values()) * item


def param_count(spec: dict) -> int:
    return sum(math.prod(s) for s in shapes(spec).values())
