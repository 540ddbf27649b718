"""Seeded weights of a configuration, made on the device.

The benchmark, not the system under test, makes the weights: the harness
uploads them (the client's checkpoint) and the plain reference makes the
same values again from the same seed, so the reference takes nothing the
program made.

Which leaves there are, by name and shape, and which of them are norm
scales, is the architecture's to say (``arch/<arch>.py`` ``shapes`` and
``is_norm``, found by `architectures` from the configuration's
``reference`` key); the names are the checkpoint layout the served
program reads, one name per array. The rest holds for every
architecture: every value is uniform, drawn from 16 random bits per
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

from chipbench import architectures

#: a norm scale is 1 + NORM_SPREAD * u with u uniform in (-1, 1)
NORM_SPREAD = 0.2


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


def shapes(spec: dict, arch=None) -> dict[str, tuple[int, ...]]:
    """Leaf name -> shape, in sorted name order, by `arch` (by default
    the architecture `spec` names)."""
    return dict(sorted(architectures.of(spec, arch).arch.shapes(spec).items()))


def _fan_in(name: str, shape: tuple[int, ...]) -> int:
    # the embedding table's rows are vectors of width D, like a matrix's
    # input side: both are scaled by 1/sqrt(D)
    return shape[-1] if name == "embed" else shape[-2]


def _leaf(key, name: str, shape: tuple[int, ...], norm: bool, dtype):
    bits = jax.random.bits(key, shape, jnp.uint16)
    u = (bits.astype(jnp.float32) + 0.5) * (2.0 / 65536.0) - 1.0
    if norm:
        return (1.0 + NORM_SPREAD * u).astype(dtype)
    return (u * (math.sqrt(3.0) / math.sqrt(_fan_in(name, shape)))
            ).astype(dtype)


@functools.lru_cache(maxsize=None)
def _maker(items: tuple, dtype: str):
    def make(key):
        return {n: _leaf(jax.random.fold_in(key, i), n, s, norm,
                         jnp.dtype(dtype))
                for i, (n, s, norm) in enumerate(items)}
    return jax.jit(make)


def make(spec: dict, seed: int, arch=None) -> dict:
    """Every leaf of `spec`'s weights from `seed`, in one jitted call, in
    the dtype the configuration serves (``torch_dtype``)."""
    arch = architectures.of(spec, arch)
    items = tuple((n, s, bool(arch.arch.is_norm(n, s)))
                  for n, s in shapes(spec, arch).items())
    return _maker(items, spec["torch_dtype"])(seed_key(seed))


def nbytes(spec: dict) -> int:
    item = jnp.dtype(spec["torch_dtype"]).itemsize
    return sum(math.prod(s) for s in shapes(spec).values()) * item


def param_count(spec: dict) -> int:
    return sum(math.prod(s) for s in shapes(spec).values())
