"""`chip_smoke.py` rehearsed on the CPU.

The phase function runs every scenario end to end at granite-8b's SMOKE
config, with the config injected and the device check bypassed here in
the test. The script itself must refuse to run without a TPU, and
without the rest of the repository, printing no result either way.
"""
import json
import os
import shutil
import subprocess
import sys

import chip_smoke

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_every_scenario_serves_at_smoke_config():
    from repro.configs.granite_8b import SMOKE
    lines = []
    with chip_smoke.CompileLog() as log:
        recs = chip_smoke.serve_all({"llm": SMOKE, "emb": SMOKE}, log,
                                    emit=lines.append)
    assert [r["scenario"] for r in recs] == list(chip_smoke.SCENARIOS)
    assert [json.loads(line)["scenario"] for line in lines] == \
        list(chip_smoke.SCENARIOS)
    for r in recs:
        assert r["failures"] == [], r
        assert r["model"] == SMOKE.name
        assert len(r["nexus_host_latency_s"]) == chip_smoke.INVOCATIONS
        assert r["nexus_cold"][0] and not any(r["nexus_cold"][1:])
        # the repeats reuse the jitted steps: nothing is built again
        assert r["nexus_executables_built"][1:] == [0] * (
            chip_smoke.INVOCATIONS - 1)
    assert log.built > 0


def _run(script_dir, env_extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(env_extra)
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=script_dir,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def test_script_refuses_the_cpu():
    r = _run(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs a TPU" in r.stderr


def test_script_alone_fails(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    r = _run(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert r.stdout == ""
