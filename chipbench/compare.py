"""The numbers that decide a run's ``correct``, each held to a limit.

Every number is a relative error in float64: the norm of the served
output's difference from the reference, over the reference's norm,
taken where it is largest. An output of the wrong shape or not finite, or a
reference of norm 0, reads `WRONG` (finite, so that the result line stays JSON).

* ``logits_rel_err``: over every row (one sequence of the batch) of the
  final-position logits;
* ``kv_rel_err``: over every layer's keys and every layer's values, each
  over all positions and heads;
* ``kv_index_err``: how many entries of the cache's position counters
  (``pos`` and ``slot_pos``) differ from a cache that holds the whole
  prompt in order; exact, so its limit is 0.
"""
from __future__ import annotations

import numpy as np

WRONG = 1e9


def rel_err(got, ref) -> float:
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    den = np.linalg.norm(ref)
    err = float(np.linalg.norm(got - ref) / den) if den else WRONG
    return err if np.isfinite(err) else WRONG


def logits_numbers(pairs) -> dict:
    """`pairs`: (served (B, 1, V), reference (B, 1, V)) per answer."""
    worst = 0.0
    for got, ref in pairs:
        if got.shape != ref.shape:
            return {"logits_rel_err": WRONG}
        for g, r in zip(got, ref):
            worst = max(worst, rel_err(g, r))
    return {"logits_rel_err": worst}


def kv_numbers(pairs) -> dict:
    """`pairs`: (served cache dict, reference {"k", "v"}) per answer; the
    served cache holds ``k``, ``v`` (L, B, S, K, hd), ``pos`` (B,) and
    ``slot_pos`` (B, S)."""
    worst, bad = 0.0, 0
    for got, ref in pairs:
        for name in ("k", "v"):
            g, r = np.asarray(got[name]), ref[name]
            if g.shape != r.shape:
                return {"kv_rel_err": WRONG,
                        "kv_index_err": WRONG}
            worst = max(worst, *(rel_err(g[i], r[i])
                                 for i in range(r.shape[0])))
        B, S = ref["k"].shape[1:3]
        pos, slot = np.asarray(got["pos"]), np.asarray(got["slot_pos"])
        if pos.shape != (B,) or slot.shape != (B, S):
            return {"kv_rel_err": worst, "kv_index_err": WRONG}
        bad += int(np.sum(pos != S))
        bad += int(np.sum(slot != np.arange(S)[None, :]))
    return {"kv_rel_err": worst, "kv_index_err": float(bad)}


#: a mix's ``compare`` kind -> the function that reads its numbers; the
#: kind also names what the reference returns (``forward``'s ``want``)
NUMBERS = {"logits": logits_numbers, "kv": kv_numbers}
