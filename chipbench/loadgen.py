"""The one traffic generator: it reads a mix from ``traffic/<name>.json``.

A mix's keys:

* ``scenario``, ``role``: the served function, by its name in the
  program's ML suite, and the role whose configuration serves it;
* ``input``, ``batch``, ``seq``: the per-invocation input (the program's
  payload kind) and its shape, its ids uniform over the vocabulary;
* ``instances``: ``warm`` keeps the function's instance between
  invocations, ``scale_to_zero`` drops it after every response, so each
  invocation restores a fresh one;
* ``output``, ``compare``: the program's name for the durable output's
  shape tree, and the kind of numbers that judge it: one of `compare`'s,
  or of the architecture's reference (`architectures`);
* ``check_sample``: how many of a window's answers the reference
  recomputes, drawn from the seed.

Every mix is driven by one closed-loop client: the next invocation is
submitted when the previous response has arrived.

Inputs depend only on the seed and the invocation's index, so the same
seed sends the same inputs; a different seed sends others of the same
sizes.
"""
from __future__ import annotations

import numpy as np

INSTANCES = ("warm", "scale_to_zero")


class Mix:
    def __init__(self, mix: dict, spec: dict, seed: int):
        if mix["instances"] not in INSTANCES:
            raise ValueError(f"unknown instances policy {mix['instances']!r}")
        self.mix = mix
        self.seed = int(seed)
        self.vocab = spec["vocab_size"]
        self.shape = (mix["batch"], mix["seq"])

    def tokens(self, i: int) -> np.ndarray:
        """Invocation `i`'s token batch (index 0 is the warm-up's)."""
        rng = np.random.default_rng([self.seed, i])
        return rng.integers(0, self.vocab, self.shape, dtype=np.int32)

    def sample(self, n_answers: int) -> list[int]:
        """Which of `n_answers` window answers the reference checks."""
        k = min(self.mix["check_sample"], n_answers)
        rng = np.random.default_rng([self.seed, 1 << 20])
        return sorted(rng.choice(n_answers, size=k, replace=False).tolist())
