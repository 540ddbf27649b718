"""Shared helpers for the benchmark harness.

Every accessor here must tolerate a missing ``results/`` directory —
fresh clones (and CI workspaces before the first bench step) have no
results yet, and a benchmark or the regression gate asking for one
should get a clean signal, not a raw ``FileNotFoundError`` traceback.
"""
from __future__ import annotations

import json
import multiprocessing
import os
from typing import Any

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "results")

#: context for the DES worker pools: each worker starts from a fresh
#: interpreter, because a parent that has touched JAX holds the
#: accelerator and a forked child would inherit that state
SPAWN = multiprocessing.get_context("spawn")


def result_path(name: str) -> str:
    """Absolute path of one result file; creates ``results/`` if absent
    so callers may open the path for writing unconditionally."""
    os.makedirs(RESULTS_DIR, exist_ok=True)
    return os.path.abspath(os.path.join(RESULTS_DIR, f"{name}.json"))


def save_json(name: str, payload: Any) -> str:
    path = result_path(name)
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, default=str)
    return path


def table(rows: list[dict], cols: list[str], *, title: str = "",
          fmt: dict | None = None) -> str:
    fmt = fmt or {}
    out = []
    if title:
        out.append(f"== {title} ==")
    # max over a list, not *args: zero rows (fresh clone, no records)
    # must render an empty table, not raise
    widths = {c: max([len(c)] + [len(_cell(r.get(c), fmt.get(c)))
                                 for r in rows]) for c in cols}
    out.append("  ".join(c.ljust(widths[c]) for c in cols))
    out.append("  ".join("-" * widths[c] for c in cols))
    for r in rows:
        out.append("  ".join(
            _cell(r.get(c), fmt.get(c)).ljust(widths[c]) for c in cols))
    return "\n".join(out)


def _cell(v, f) -> str:
    if v is None:
        return "-"
    if f:
        return format(v, f)
    if isinstance(v, float):
        return f"{v:.3g}"
    return str(v)


def pct(new: float, base: float) -> float:
    """Reduction of `new` vs `base` in percent (positive = saving)."""
    return (1.0 - new / base) * 100.0 if base else float("nan")
