"""MLServe (ISSUE 5): the ML-inference suite through the serverless core.

Prices the calibrated full-scale scenarios (`workloads.ml_suite`) under
every system variant and reports:

* warm/cold zero-contention latency per scenario (pure PhasePlan
  critical-path math over the calibrated durations — deterministic,
  which is what lets the CI regression gate pin this file tightly);
* the LLM-COLD breakdown: how much of the weights-shard fetch the
  hinted ingress prefetch hides behind the snapshot restore (§4.2.2
  applied to model loading — the paper's motivation case);
* deployment density for the ML mix via the DES (quick: fixed-n
  probes; full: a `find_density` search per variant).

``--quick`` is the CI mode: no wall-clock-sensitive numbers, safe to
diff against the committed baseline with tight tolerances.
"""
from __future__ import annotations

import os
from concurrent.futures import ProcessPoolExecutor

from repro.core.calibrate import ML_ROLES, load_calibration
from repro.core.des import DensitySimulator, find_density
from repro.core.plan import SYSTEMS, compile_plan, phase_durations
from repro.core.workloads import ML_SCENARIO_NAMES, ml_suite

from benchmarks.common import SPAWN, pct, save_json, table

SYSTEMS_ORDER = ("baseline", "nexus-tcp", "nexus-prefetch-only",
                 "nexus-async", "nexus", "nexus-sdk-only", "wasm")

#: ML invocations are heavyweight (hundreds of MB of I/O) — the
#: density experiment arrives them correspondingly slower than the
#: paper's synthetic mix.
MEAN_RATE = 0.25


def _latency_ms(system: str, w, cold: bool) -> float:
    spec = SYSTEMS[system]
    plan = compile_plan(spec, w.profile, cold=cold)
    return plan.critical_path(phase_durations(spec, w, cold)) * 1e3


def latency_tables(suite) -> tuple[list[dict], list[dict]]:
    warm_rows, cold_rows = [], []
    for name in ML_SCENARIO_NAMES:
        w = suite[name]
        wr = {"scenario": name}
        cr = {"scenario": name}
        for s in SYSTEMS_ORDER:
            wr[s] = round(_latency_ms(s, w, cold=False), 2)
            cr[s] = round(_latency_ms(s, w, cold=True), 2)
        warm_rows.append(wr)
        cold_rows.append(cr)
    return warm_rows, cold_rows


def llm_cold_breakdown(suite) -> list[dict]:
    """Where LLM-COLD's time goes, per variant: the gap between the
    serial phase sum and the critical path is the overlap the plan
    buys — dominated by weights-prefetch-during-restore."""
    w = suite["LLM-COLD"]
    rows = []
    for s in SYSTEMS_ORDER:
        spec = SYSTEMS[s]
        durs = phase_durations(spec, w, cold=True)
        plan = compile_plan(spec, w.profile, cold=True)
        critical = plan.critical_path(durs)
        serial = sum(durs.values())
        fetch0 = durs.get("fetch_cpu[0]", 0.0) + durs.get("fetch_net[0]", 0.0)
        rows.append({
            "system": s,
            "cold_ms": round(critical * 1e3, 2),
            "serial_ms": round(serial * 1e3, 2),
            "hidden_ms": round((serial - critical) * 1e3, 2),
            "restore_ms": round(durs["restore"] * 1e3, 2),
            "shard0_fetch_ms": round(fetch0 * 1e3, 2),
            "prefetched": bool(spec.prefetch),
        })
    base = rows[0]["cold_ms"]
    for r in rows:
        r["cold_vs_base_%"] = round(pct(r["cold_ms"], base), 1)
    return rows


def cache_reuse_rows() -> list[dict]:
    """SharedCache acceptance rows (ISSUE 10): LLM-DECODE's per-step
    KV GET/PUT chain and LLM-COLD's weight shards become hits on the
    SECOND invocation on a node. Tiny scale (real tensors), serial
    invokes — every count is deterministic."""
    from repro.core.cache import CacheSpec
    from repro.core.runtime import WorkerNode
    from repro.models import serving

    suite = ml_suite("tiny")
    rows = []
    for name in ("LLM-DECODE", "LLM-COLD"):
        node = WorkerNode("nexus", byte_scale=1.0, cache=CacheSpec())
        try:
            node.deploy(suite[name])
            node.seed_input(name, payloads=serving.seed_payloads(name))
            node.invoke(name).result(timeout=120)
            first = dict(node.cache_stats())
            node.invoke(name).result(timeout=120)
            second = node.cache_stats()
            rows.append({
                "scenario": name,
                "gets": len(suite[name].profile.gets),
                "first_inv_hits": first["hits"],
                "second_inv_hits": second["hits"] - first["hits"],
                "lookups": second["lookups"],
                "misses": second["misses"],
                "writes": second["writes"]})
        finally:
            node.shutdown()
    return rows


def _probe(system: str, n: int, duration: float, suite) -> dict:
    r = DensitySimulator(system, n, seed=1, duration_s=duration,
                         warmup_s=5.0, mean_rate=MEAN_RATE,
                         suite=suite).run()
    return {"system": system, "n": n,
            "completed": r.completed, "cold": r.cold_starts,
            "slowdown": round(r.geomean_slowdown(), 3),
            "cpu_util": round(r.cpu_util, 3),
            "mem_util": round(r.mem_util, 3),
            "pass": r.meets_slo()}


def _search(args) -> dict:
    system, duration = args
    best, results = find_density(
        system, lo=20, hi=400, step=20, seed=1, refine_to=4,
        duration_s=duration, warmup_s=5.0, mean_rate=MEAN_RATE,
        suite=ml_suite("full"))
    return {"system": system, "density": best, "probes": len(results)}


def run(quick: bool = False) -> dict:
    suite = ml_suite("full")
    cal = load_calibration()

    cal_rows = []
    for role, arch in ML_ROLES.items():
        entry = cal["models"][f"full/{role}"]
        cal_rows.append({
            "role": role, "arch": arch,
            "params_MB": round(entry["params_bytes"] / 1e6, 1),
            **{p: round(entry["phases"][p]["mcycles"], 2)
               for p in ("prefill", "decode", "encode")}})

    warm_rows, cold_rows = latency_tables(suite)
    bd_rows = llm_cold_breakdown(suite)
    cache_rows = cache_reuse_rows()

    print(table(cal_rows, ["role", "arch", "params_MB", "prefill",
                           "decode", "encode"],
                title="calibration (per-device Mcyc at 2.1 GHz; "
                      f"machine={cal['machines']['full']['name']})"))
    print()
    print(table(warm_rows, ["scenario"] + list(SYSTEMS_ORDER),
                title="warm zero-contention latency (ms)"))
    print()
    print(table(cold_rows, ["scenario"] + list(SYSTEMS_ORDER),
                title="cold zero-contention latency (ms)"))
    print()
    print(table(bd_rows, ["system", "cold_ms", "cold_vs_base_%",
                          "serial_ms", "hidden_ms", "restore_ms",
                          "shard0_fetch_ms", "prefetched"],
                title="LLM-COLD breakdown: weights prefetch hidden "
                      "behind the snapshot restore"))
    print()
    print(table(cache_rows, ["scenario", "gets", "first_inv_hits",
                             "second_inv_hits", "lookups", "misses",
                             "writes"],
                title="SharedCache: second-invocation reuse "
                      "(threaded node, tiny scale)"))

    if quick:
        duration = 20.0
        density_rows = [_probe(s, 40, duration, suite)
                        for s in SYSTEMS_ORDER]
        print()
        print(table(density_rows,
                    ["system", "n", "completed", "cold", "slowdown",
                     "cpu_util", "mem_util", "pass"],
                    title=f"DES probe at n=40 (quick; rate={MEAN_RATE}/s)"))
    else:
        duration = 40.0
        jobs = [(s, duration) for s in SYSTEMS_ORDER]
        workers = min(os.cpu_count() or 1, len(jobs))
        with ProcessPoolExecutor(max_workers=workers, mp_context=SPAWN) as pool:
            density_rows = list(pool.map(_search, jobs))
        base = max(density_rows[0]["density"], 1)
        for r in density_rows:
            r["gain_%"] = round((r["density"] / base - 1) * 100, 1)
        print()
        print(table(density_rows, ["system", "density", "gain_%", "probes"],
                    title="ML-suite deployment density (p99 < 5x unloaded)"))

    payload = {"calibration": cal_rows, "warm": warm_rows,
               "cold": cold_rows, "llm_cold_breakdown": bd_rows,
               "cache_reuse": cache_rows,
               "density": density_rows,
               "config": {"quick": quick, "mean_rate": MEAN_RATE,
                          "systems": list(SYSTEMS_ORDER)}}
    save_json("ml_serving", payload)
    return payload


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    run(quick=ap.parse_args().quick)
