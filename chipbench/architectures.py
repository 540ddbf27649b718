"""Find a configuration's architecture by the name in its ``reference`` key.

A configuration file (``configs/<name>.json``) names its architecture,
as in ``"reference": "llama"``. An architecture is two modules, found by
path under the benchmark's directory, as the metric readers are:

* ``reference/<arch>.py``, the plain float32 reference, which imports
  nothing of the program. It defines ``forward(spec, w, tokens, *,
  want, precision, row_block)``, as `reference.llama` does, and may
  define ``NUMBERS``: compare kinds beyond `compare.NUMBERS`. The
  harness pairs such a kind's output as it pairs ``kv``'s: the served
  tree whole against the reference's dict, and for the control the
  reference's dict with the ``pos`` and ``slot_pos`` of a cache that
  holds the whole prompt. A kind that needs another pairing edits
  `harness.check`.
* ``arch/<arch>.py``, what the harness needs to know of the
  architecture; besides the harness, the only module that imports the
  program. It defines ``program_config(spec)``, the program's
  ``ModelConfig``; ``shapes(spec)``, leaf name -> shape of the weights;
  ``is_norm(name, shape)``, whether `reference.weights` makes a leaf as
  a norm scale; and ``prefill_flops(spec, batch, seq)`` (see `flops`).

A new architecture adds these two files and edits none. Each pair is
loaded once per directory and name: `harness.load_cell` finds a cell's
under its checkout and carries it on the cell.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import os
import re
from types import ModuleType

from chipbench import compare

HERE = os.path.dirname(os.path.abspath(__file__))

#: what each of an architecture's two modules has to define
REQUIRED = {"reference": ("forward",),
            "arch": ("program_config", "shapes", "is_norm", "prefill_flops")}


class Unknown(LookupError):
    """No architecture by that name, or one whose modules lack a
    required function."""


@dataclasses.dataclass(frozen=True)
class Architecture:
    name: str
    reference: ModuleType       # reference/<name>.py
    arch: ModuleType            # arch/<name>.py

    def numbers(self, kind: str):
        """The function that reads the compared numbers of a mix's
        ``compare`` kind."""
        return {**compare.NUMBERS,
                **getattr(self.reference, "NUMBERS", {})}[kind]


_FOUND: dict[tuple[str, str], Architecture] = {}


def load_module(path: str, name: str) -> ModuleType:
    """The module in file `path`, loaded as `name` and not registered in
    ``sys.modules``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load(root: str, part: str, name: str) -> ModuleType:
    path = os.path.join(root, part, f"{name}.py")
    if not os.path.isfile(path):
        raise Unknown(f"architecture {name!r}: no {part}/{name}.py in {root}")
    mod = load_module(path, f"chipbench_{part}_{name}")
    missing = [f for f in REQUIRED[part] if not callable(getattr(mod, f, None))]
    if missing:
        raise Unknown(f"architecture {name!r}: {part}/{name}.py defines no "
                      f"{', '.join(missing)}")
    return mod


def find(name, root: str = HERE) -> Architecture:
    """The architecture `name`, its two modules found under `root`."""
    if not isinstance(name, str) or not re.fullmatch(
            r"[A-Za-z0-9_][A-Za-z0-9_-]*", name):
        raise Unknown(f"{name!r} names no architecture")
    key = (os.path.abspath(root), name)
    if key not in _FOUND:
        _FOUND[key] = Architecture(name, _load(root, "reference", name),
                                   _load(root, "arch", name))
    return _FOUND[key]


def of(spec: dict, arch: Architecture | None = None) -> Architecture:
    """`arch` where given (a cell's, from `harness.load_cell`), else the
    architecture a configuration names by its ``reference`` key, found
    in this benchmark's directory."""
    return arch if arch is not None else find(spec.get("reference"))
