"""Helpers for the benchmark's own CPU tests.

Run them by path from the root of the checkout::

    python -m pytest -q chipbench/tests

The cells run here at each configuration's SMOKE size: every width cut
as the program's own SMOKE configurations cut them (``smoke/<config>.json``),
and a name ending in ``-smoke`` so the program serves its tiny shapes.
The cells and configurations are those of ``BENCHMARK.json``: a new one
adds its files, and the tests that hold for any of them take it.
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import json  # noqa: E402

import pytest  # noqa: E402

from chipbench import harness  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
#: every cell and every configuration of ``BENCHMARK.json``
CELLS = tuple(w["name"] for w in BENCH["workloads"])
CONFIGS = tuple(c["name"] for c in BENCH["configs"])

FAKE_TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def config_spec(config: str) -> dict:
    """Configuration `config`'s file as the cells run it."""
    conf = {c["name"]: c for c in BENCH["configs"]}[config]
    with open(os.path.join(ROOT, conf["file"])) as f:
        return json.load(f)


def smoke_spec(spec: dict) -> dict:
    """`spec` at the program's SMOKE widths of its model
    (``smoke/<config>.json``)."""
    with open(os.path.join(os.path.dirname(__file__), "smoke",
                           f"{spec['name']}.json")) as f:
        return dict(spec, name=spec["name"] + "-smoke", **json.load(f))


def smoke_cell(name: str, root: str = ROOT) -> "harness.Cell":
    """`name`'s cell at its configuration's SMOKE size: the program's
    tiny serving shape of the mix's input, and every answer of the
    window checked."""
    from repro.models import serving
    cell = harness.load_cell(name, root)
    cell.spec = smoke_spec(cell.spec)
    structs = serving.bundle(
        harness.program_config(cell.spec, cell.arch))["structs"]
    batch, seq = structs[cell.mix["input"]].shape
    cell.mix = dict(cell.mix, batch=batch, seq=seq, check_sample=100)
    return cell


@pytest.fixture(params=CELLS)
def cell(request):
    return smoke_cell(request.param)
