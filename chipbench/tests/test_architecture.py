"""A configuration's architecture is found by its ``reference`` key.

An architecture planted outside ``chipbench/`` runs a whole cell through
its own two modules; a ``reference`` that names no architecture, or
modules that lack what the harness calls, exit 2 with no result; and
yi-34b-4l's weights and counts are those of the llama-only harness that
came before the lookup.
"""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from chipbench import architectures, flops, harness
from chipbench.reference import weights
from chipbench.tests.conftest import (FAKE_TPU, config_spec, smoke_cell,
                                      smoke_spec)

#: sha256 of `serialize.dumps` of yi-34b-4l's SMOKE weights from seed
#: 2**31 + 7, recorded with the harness as it was before architectures
#: were looked up, when `reference/weights.py` listed llama's leaves
YI_SMOKE_WEIGHTS_SHA256 = (
    "e6d8f939f491d10218cb1f9c50bce9a285e4b457ac0e81beb6263837d452f292")
#: yi-34b-4l's parameters and operations a step, from the same code
YI_PARAMS = 3_148_938_240
YI_FLOPS = {
    (32, 512): {"layers": 73117523247104.0, "attention": 481036337152.0,
                "head": 29360128000.0, "total": 73627919712256.0},
    (1, 2048): {"layers": 9139690405888.0, "attention": 240518168576.0,
                "head": 917504000.0, "total": 9381126078464.0},
}

#: a toy architecture whose two modules record each call and hand it to
#: llama's
TOY_ARCH = '''
from chipbench import architectures

CALLS = []
_llama = architectures.find("llama").arch


def _recorded(name):
    def call(*args, **kw):
        CALLS.append(name)
        return getattr(_llama, name)(*args, **kw)
    return call


program_config = _recorded("program_config")
shapes = _recorded("shapes")
is_norm = _recorded("is_norm")
prefill_flops = _recorded("prefill_flops")
'''
TOY_REFERENCE = '''
from chipbench import architectures

CALLS = []


def forward(*args, **kw):
    CALLS.append("forward")
    return architectures.find("llama").reference.forward(*args, **kw)
'''


def _plant(root, name, arch, reference, config="yi-34b-4l"):
    """A checkout's files at `root`: ``BENCHMARK.json``, `config`'s file
    naming architecture `name`, and that architecture's two modules."""
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), root)
    here = os.path.join(root, "chipbench")
    for part, text in (("arch", arch), ("reference", reference),
                       ("configs", None)):
        os.makedirs(os.path.join(here, part), exist_ok=True)
        if text is not None:
            with open(os.path.join(here, part, f"{name}.py"), "w") as f:
                f.write(text)
    with open(os.path.join(here, "configs", f"{config}.json"), "w") as f:
        json.dump(dict(config_spec(config), reference=name), f)


def _files(top):
    """Every file under `top` with the digest of its bytes, as git would
    see them (no ``__pycache__``)."""
    out = {}
    for d, dirs, fs in os.walk(top):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for f in fs:
            with open(os.path.join(d, f), "rb") as fh:
                out[os.path.join(d, f)] = hashlib.sha256(fh.read()).digest()
    return out


@pytest.fixture
def lookups(monkeypatch):
    """Architectures found inside the test are forgotten after it."""
    monkeypatch.setattr(architectures, "_FOUND", dict(architectures._FOUND))


@pytest.mark.parametrize("name", ["toy", "llama"])
def test_planted_architecture_runs_a_cell(tmp_path, lookups, name):
    """Under a new name, and under the name of one this checkout has:
    each checkout's cells run through the modules of its own."""
    before = _files(harness.HERE)
    architectures.find("llama")
    _plant(tmp_path, name, TOY_ARCH, TOY_REFERENCE)
    cell = smoke_cell("yi-emb-warm", root=str(tmp_path))
    assert cell.spec["reference"] == name
    res = harness.run_cell(cell, 2**31 + 13, 1.0, False, 0.0, FAKE_TPU,
                           log=lambda *_: None)
    assert res["correct"], res["checks"]
    toy = cell.arch
    assert os.path.dirname(toy.arch.__file__) == str(tmp_path / "chipbench"
                                                    / "arch")
    assert set(toy.arch.CALLS) == {"program_config", "shapes", "is_norm",
                                   "prefill_flops"}
    assert toy.reference.CALLS
    assert not hasattr(architectures.find("llama").arch, "CALLS")
    assert _files(harness.HERE) == before


def test_reference_adds_compare_kinds(tmp_path, lookups):
    """A reference's own ``NUMBERS`` add kinds; `compare`'s stay."""
    from chipbench import compare
    _plant(tmp_path, "latent", TOY_ARCH, TOY_REFERENCE + (
        "NUMBERS = {'latent': lambda pairs: {'latent_rel_err': 0.0}}\n"))
    arch = architectures.find("latent", str(tmp_path / "chipbench"))
    assert arch.numbers("latent")([]) == {"latent_rel_err": 0.0}
    assert arch.numbers("kv") is compare.kv_numbers
    assert arch.numbers("logits") is compare.logits_numbers


FUNCS = "def program_config(spec): pass\ndef is_norm(name, shape): pass\n" \
        "def prefill_flops(spec, batch, seq): pass\n"


@pytest.mark.parametrize("reference,arch,text,match", [
    ("nope", None, None, "no reference/nope.py"),
    ("bad", FUNCS, "def forward(*a, **k): pass\n", "defines no shapes"),
    ("bad", FUNCS + "def shapes(spec): pass\n", "", "defines no forward"),
], ids=["unknown", "no-shapes", "no-forward"])
def test_missing_architecture_exits_2(tmp_path, reference, arch, text,
                                      match):
    """In a checkout of the benchmark alone, where the run would stop at
    the program's code if it got that far."""
    shutil.copytree(os.path.join(harness.ROOT, "chipbench"),
                    tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    _plant(tmp_path, reference, arch, text)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "yi-emb-warm",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 2, p.stderr
    assert match in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_weights_are_bit_for_bit_the_parents():
    from repro.models import serialize
    spec = smoke_spec(config_spec("yi-34b-4l"))
    w = {n: np.asarray(a) for n, a in weights.make(spec, 2**31 + 7).items()}
    digest = hashlib.sha256(serialize.dumps(w)).hexdigest()
    assert digest == YI_SMOKE_WEIGHTS_SHA256
    assert weights.param_count(config_spec("yi-34b-4l")) == YI_PARAMS


@pytest.mark.parametrize("shape", sorted(YI_FLOPS))
def test_flops_are_the_parents(shape):
    assert flops.prefill_flops(config_spec("yi-34b-4l"), *shape) \
        == YI_FLOPS[shape]
