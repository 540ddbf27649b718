"""MLServe acceptance (ISSUE 5): the model stack through the core.

Four contracts:

* **Calibration** — ``calibration.json`` regenerates deterministically
  from the analytic FLOPs machinery and matches the committed file;
  the tiny-scale byte sizes it declares are exactly the bytes the
  handlers read and write.
* **Transparency** — ONE handler code object per scenario runs under
  all 7 `SYSTEMS` variants over REAL tensors (params/KV serialized
  through ``ctx.storage``), and its durable outputs are byte-identical
  across every variant; the LLM-DECODE KV round-trip matches a direct
  model execution bit-for-bit.
* **Cross-executor parity** — the DES walks the same compiled plans:
  zero-contention latency == the plan's critical path for every
  (variant x ml scenario x coldness), at BOTH scales.
* **Purity** — building the suite never imports jax (the DES prices
  profiles as pure data), and the ML scenarios stay out of `REGISTRY`
  (paper denominators and parity goldens must not move).
"""
import math
import sys

import pytest

from repro.core import workloads as W
from repro.core.calibrate import load_calibration
from repro.core.des import DensitySimulator
from repro.core.plan import SYSTEMS, compile_plan, phase_durations
from repro.core.runtime import WorkerNode
from repro.core.workloads import ML_SCENARIO_NAMES, ml_suite

ALL_SYSTEMS = tuple(SYSTEMS)


# ------------------------------------------------------------- calibration

class TestCalibration:
    def test_regeneration_is_deterministic_and_committed(self):
        """Deriving the calibration twice gives identical trees, and
        the committed calibration.json is exactly that derivation —
        regeneration can never silently move the cost model."""
        from repro.core.calibrate import derive_calibration
        a = derive_calibration()
        b = derive_calibration()
        assert a == b
        assert a == load_calibration()

    def test_tiny_sizes_are_exact_payload_sizes(self):
        """The declared GET sizes at tiny scale are byte-exact against
        the real serialized payloads the handlers consume."""
        from repro.models import serving
        suite = ml_suite("tiny")
        for name, w in suite.items():
            payloads = serving.seed_payloads(name)
            declared = [g.size_bytes for g in w.profile.gets]
            assert declared == [len(p) for p in payloads], name

    def test_suite_at_a_config_declares_its_exact_sizes(self):
        """`ml_suite_at` serves a chosen config through the same handler
        objects; at the SMOKE configs its GET/PUT sizes are exactly the
        committed tiny calibration's, and each workload names the
        config its handler is handed."""
        from repro.configs import registry
        from repro.core.calibrate import ML_ROLES
        from repro.core.workloads import ml_suite_at
        models = {r: registry.get_smoke(a) for r, a in ML_ROLES.items()}
        at, tiny = ml_suite_at(models), ml_suite("tiny")
        assert list(at) == list(tiny)
        for name, w in at.items():
            t = tiny[name]
            assert w.handler is t.handler and t.model is None
            assert w.model is models[
                "emb" if name == "EMB" else "moe" if name == "MOE" else "llm"]
            assert [g.size_bytes for g in w.profile.gets] == \
                [g.size_bytes for g in t.profile.gets]
            assert [p.size_bytes for p in w.profile.puts] == \
                [p.size_bytes for p in t.profile.puts]

    def test_full_scale_is_serving_sized(self):
        """The full-scale suite carries the paper's motivation: weight
        shards are hundreds of MB, decode KV state is tens-to-hundreds
        of MB — the I/O that makes offload matter."""
        suite = ml_suite("full")
        shard0 = suite["LLM-COLD"].profile.gets[0].size_bytes
        assert shard0 > 100 * W.MB
        kv = suite["LLM-DECODE"].profile.gets[1].size_bytes
        assert kv > 10 * W.MB

    def test_calibrated_not_hand_picked(self):
        """Every ComputeSegment budget is the machine-profile roofline
        over the analytic per-model FLOPs — reconstructable from the
        committed database, never a hard-coded constant."""
        cal = load_calibration()
        for scale in ("full", "tiny"):
            suite = ml_suite(scale)
            llm = cal["models"][f"{scale}/llm"]
            ph = {p: llm["phases"][p]["mcycles"] for p in llm["phases"]}
            segs = suite["LLM-PREFILL"].profile.segments
            assert segs[0].mcycles == ph["prefill"]
            segs = suite["LLM-DECODE"].profile.segments
            assert segs[0].mcycles == ph["decode"]
            segs = suite["LLM-COLD"].profile.segments
            assert segs[0].mcycles == ph["prefill"] + ph["decode"]

    def test_unknown_scale_rejected(self):
        with pytest.raises(ValueError):
            ml_suite("medium")

    def test_suite_is_pure_data(self):
        """Building either scale must not import jax: the DES and the
        benchmark tables price profiles straight from calibration.json.
        Checked in a fresh interpreter so in-process import order
        cannot mask a regression."""
        import os
        import subprocess
        code = ("import sys\n"
                "from repro.core.workloads import ml_suite\n"
                "ml_suite('full'); ml_suite('tiny')\n"
                "bad = [m for m in sys.modules\n"
                "       if m == 'jax' or m.startswith('jax.')]\n"
                "assert not bad, bad\n")
        env = dict(os.environ)
        src = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "src")
        env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr

    def test_ml_suite_not_in_registry(self):
        assert not set(ML_SCENARIO_NAMES) & set(W.REGISTRY)


# ------------------------------------------------------------ transparency

def _run_ml(system: str, suite, name: str):
    """One invocation of a tiny-scale ML scenario: returns the durable
    outputs (in PUT order) and the InvocationResult."""
    from repro.models import serving
    node = WorkerNode(system, byte_scale=1.0)
    try:
        node.deploy(suite[name])
        node.seed_input(name, payloads=serving.seed_payloads(name))
        res = node.invoke(name).result(timeout=120)
        outs = []
        for k in range(len(suite[name].profile.puts)):
            key = f"{res.invocation_id}-out" + ("" if k == 0 else f"-{k}")
            outs.append(bytes(node.store.get("out", key)))
        return outs, res
    finally:
        node.shutdown()


class TestTransparency:
    @pytest.mark.parametrize("name", ML_SCENARIO_NAMES)
    def test_byte_identical_outputs_across_all_variants(self, name):
        """The acceptance claim: the SAME handler code object, fed the
        SAME real tensors through whatever client the variant injects,
        leaves byte-identical durable state under all 7 variants."""
        suite = ml_suite("tiny")
        # one code object across scales and variants — transparency is
        # a property of the handler, not of a per-variant port
        assert suite[name].handler is ml_suite("full")[name].handler

        reference = None
        for system in ALL_SYSTEMS:
            outs, res = _run_ml(system, suite, name)
            assert res.response["statusCode"] == 200, (system, name)
            declared = [p.size_bytes for p in suite[name].profile.puts]
            assert [len(o) for o in outs] == declared, (system, name)
            if reference is None:
                reference = outs
            else:
                assert outs == reference, (system, name)

    def test_decode_kv_round_trip_is_bit_exact(self):
        """The KV cache written back by the LLM-DECODE handler equals a
        direct model execution over the same seed state — the platform
        moved the tensors, it never touched them."""
        from repro.models import serving
        suite = ml_suite("tiny")
        payloads = serving.seed_payloads("LLM-DECODE")
        kv_direct, token_direct = serving.llm_decode(payloads[0],
                                                     payloads[1])
        outs, res = _run_ml("nexus", suite, "LLM-DECODE")
        assert outs[0] == kv_direct
        assert res.response["token"] == token_direct

    def test_codec_round_trip(self):
        """serialize: loads(dumps(x)) is the identity, and sizes agree
        with the shape arithmetic calibration relies on."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from repro.models import serialize
        tree = {"a": jnp.arange(6, dtype=jnp.int32).reshape(2, 3),
                "b": (jnp.ones((4,), jnp.bfloat16),
                      jnp.zeros((2, 2), jnp.float32))}
        blob = serialize.dumps(tree)
        shapes = jax.eval_shape(lambda: tree)
        assert len(blob) == serialize.tree_nbytes(shapes)
        back = serialize.loads(shapes, blob)
        for x, y in zip(jax.tree_util.tree_leaves(tree),
                        jax.tree_util.tree_leaves(back)):
            assert x.dtype == y.dtype and x.shape == y.shape
            assert np.array_equal(np.asarray(x), np.asarray(y))
        with pytest.raises(ValueError):
            serialize.loads(shapes, blob + b"x")


# ----------------------------------------------------- cross-executor parity

class TestDESParity:
    @pytest.mark.parametrize("system", ALL_SYSTEMS)
    @pytest.mark.parametrize("scale", ["full", "tiny"])
    def test_zero_contention_matches_critical_path(self, system, scale):
        """DES latency with effectively infinite resources equals the
        compiled plan's critical path over the calibrated durations —
        per variant, per ml scenario, cold AND warm."""
        suite = ml_suite(scale)
        spec = SYSTEMS[system]
        for cold in (False, True):
            sim = DensitySimulator(system, len(suite), seed=0,
                                   duration_s=5.0, warmup_s=0.0,
                                   cores=4096, backend_workers=4096,
                                   nodes=1, mem_gb=4096.0, suite=suite)
            for fn in sim.functions:
                inst = sim._spawn(fn)
                assert inst is not None
                inst.state = "busy"
                sim._execute(inst, 0.0, cold=cold)
            # full-scale baseline invocations take minutes of virtual
            # time (2 GB through the in-guest SDK) — drain far enough
            sim.loop.run(3600.0)
            for fn in sim.functions:
                w = sim.workload[fn]
                cp = compile_plan(spec, w.profile, cold=cold).critical_path(
                    phase_durations(spec, w, cold=cold))
                assert len(sim.latencies[fn]) == 1, (fn, cold)
                assert math.isclose(sim.latencies[fn][0], cp,
                                    rel_tol=1e-9), (fn, cold)

    def test_loaded_full_scale_run_completes(self):
        """A contended full-scale ML deployment runs end to end in the
        DES and the offloaded variant sustains it comfortably."""
        r = DensitySimulator("nexus", 20, seed=1, duration_s=15.0,
                             warmup_s=3.0, mean_rate=0.25,
                             suite=ml_suite("full")).run()
        assert r.completed > 0
        assert r.meets_slo()

    def test_prefetch_hides_restore_in_llm_cold(self):
        """The LLM-COLD story: under prefetch variants the cold
        critical path is shorter than the serial phase sum by at least
        (almost all of) the restore — the weights-shard prefetch runs
        behind it. Non-prefetch offloaded variants get no such overlap."""
        suite = ml_suite("full")
        w = suite["LLM-COLD"]
        for system, overlapped in (("nexus", True), ("nexus-async", True),
                                   ("nexus-tcp", False)):
            spec = SYSTEMS[system]
            durs = phase_durations(spec, w, cold=True)
            cp = compile_plan(spec, w.profile, cold=True).critical_path(durs)
            hidden = sum(durs.values()) - cp
            if overlapped:
                # restore is cheaper than the shard-0 fetch chain, so
                # the whole restore hides behind the prefetch
                assert hidden == pytest.approx(durs["restore"], rel=1e-9)
            else:
                assert hidden < durs["restore"] * 0.1
