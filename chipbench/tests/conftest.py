"""Helpers for the benchmark's own CPU tests.

Run them by path from the root of the checkout::

    python -m pytest -q chipbench/tests

The cells run here at each configuration's SMOKE size: every width cut
as the program's own SMOKE configurations cut them, and a name ending
in ``-smoke`` so the program serves its tiny shapes.
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

from chipbench import harness  # noqa: E402

#: the program's SMOKE widths of each configuration's model
SMOKE = {
    "yi-34b-4l": dict(hidden_size=112, num_attention_heads=7,
                      num_key_value_heads=1, head_dim=16,
                      intermediate_size=224, vocab_size=512,
                      num_hidden_layers=2),
}
#: the program's tiny serving shapes (``calibrate.SERVING_SHAPES``)
TINY = {"enc_tokens": (4, 16), "prompt": (1, 32)}
CELLS = ("yi-emb-warm", "yi-prefill-coldvm")

FAKE_TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def smoke_spec(spec: dict) -> dict:
    return dict(spec, name=spec["name"] + "-smoke", **SMOKE[spec["name"]])


def smoke_cell(name: str) -> "harness.Cell":
    """`name`'s cell at its configuration's SMOKE size: tiny shapes,
    and every answer of the window checked."""
    cell = harness.load_cell(name)
    shape = TINY[cell.mix["input"]]
    cell.spec = smoke_spec(cell.spec)
    cell.mix = dict(cell.mix, batch=shape[0], seq=shape[1],
                    check_sample=100)
    return cell


@pytest.fixture(params=CELLS)
def cell(request):
    return smoke_cell(request.param)
