"""ClusterSim — fleet-scale dispatch policy sweep (ISSUE 9).

The single-node benches pin Nexus's per-box density win; this sweep
asks the fleet question: once a frontend spreads one arrival stream
over a heterogeneous cluster, how much of the outcome is the dispatch
policy's? Every cell runs `repro.core.cluster.ClusterSimulator` — one
virtual clock, per-node hot-engine `DensitySimulator`s — over a
policy x fleet-size x arrival-pattern matrix, sharded across processes
the way the density matrix is.

Fleet shape per size ``n``: ~1/8 fat baseline boxes, ~1/4 nexus-async,
the rest nexus (the paper's §6 mixed-estate framing), with the
function population scaled 10 functions per box so every size runs at
a comparable per-core load.

Everything is a pure function of (SEED, config): counts gate exactly
in ``scripts/check_bench.py`` (rel_tol 0.0, like overload). The
``distinct`` block asserts the acceptance bar — at the headline fleet
(largest n, azure arrivals) at least 3 policies must produce distinct
(goodput, p99) outcomes, i.e. the policy lever is visible, not noise.
"""
from __future__ import annotations

import os
import time
from concurrent.futures import ProcessPoolExecutor

from repro.core.cluster import ClusterSimulator, ClusterSpec, NodeSpec

from benchmarks.common import SPAWN, save_json, table

#: `single` is the parity anchor, not a fleet policy — swept separately
#: in the goldens; the bench compares the five real strategies.
POLICIES = ("round_robin", "random", "least_loaded", "jbsq", "affinity")

SEED = 5
FNS_PER_NODE = 10
MEAN_RATE = 1.0


def fleet(n: int) -> tuple[NodeSpec, ...]:
    """Heterogeneous fleet of ``n`` boxes (n >= 4): ~1/8 fat `baseline`
    nodes, ~1/4 `nexus-async`, the remainder `nexus` — so least-loaded's
    capacity awareness and affinity's keep-alive locality both have
    something real to exploit."""
    n_base = max(1, n // 8)
    n_async = max(1, n // 4)
    n_nexus = n - n_base - n_async
    slim = dict(nodes=1, cores=8, mem_gb=16.0, backend_workers=16,
                max_vms_per_node=70)
    return (
        NodeSpec("nexus", count=n_nexus, **slim),
        NodeSpec("nexus-async", count=n_async, **slim),
        NodeSpec("baseline", count=n_base, nodes=1, cores=16,
                 mem_gb=24.0, backend_workers=16, max_vms_per_node=100),
    )


def _cell(args) -> tuple[tuple, dict]:
    (policy, n_nodes, pattern, duration, warmup) = args
    spec = ClusterSpec(
        nodes=fleet(n_nodes), n_functions=FNS_PER_NODE * n_nodes,
        policy=policy, mean_rate=MEAN_RATE, duration_s=duration,
        warmup_s=warmup, arrival_pattern=pattern)
    r = ClusterSimulator(spec, seed=SEED).run()
    util = r.node_utilization()
    return (policy, n_nodes, pattern), {
        "offered": r.offered,
        "completed": r.completed,
        "goodput": r.goodput,
        "slo_violations": r.slo_violations,
        "cold_starts": r.cold_starts,
        "shed": r.shed_total,
        "p50_ms": round(r.p50 * 1e3, 3),
        "p99_ms": round(r.p99 * 1e3, 3),
        "util_mean": round(sum(util) / len(util), 4),
        "util_spread": round(max(util) - min(util), 4),
    }


def run(quick: bool = False) -> dict:
    duration = 15.0 if quick else 30.0
    warmup = 3.0 if quick else 6.0
    sizes = (4, 16) if quick else (4, 16, 48)
    patterns = ("azure", "poisson") if quick \
        else ("azure", "poisson", "bursty", "diurnal")

    jobs = [(pol, n, pat, duration, warmup)
            for pat in patterns for n in sizes for pol in POLICIES]
    workers = min(os.cpu_count() or 1, len(jobs))
    t0 = time.time()
    with ProcessPoolExecutor(max_workers=workers, mp_context=SPAWN) as pool:
        cells = list(pool.map(_cell, jobs))
    wall = time.time() - t0

    matrix: dict[str, dict] = {}
    for (pol, n, pat), m in cells:
        matrix.setdefault(pat, {}).setdefault(str(n), {})[pol] = m

    # headline: largest fleet, azure arrivals — the acceptance bar asks
    # for >= 3 policies with distinct deterministic goodput/p99 there
    head_n = str(max(sizes))
    head = matrix["azure"][head_n]
    outcomes = {pol: (m["goodput"], m["p99_ms"]) for pol, m in head.items()}
    distinct = {
        "n_nodes": max(sizes),
        "pattern": "azure",
        "distinct_outcomes": len(set(outcomes.values())),
        "policies": len(POLICIES),
    }
    if distinct["distinct_outcomes"] < 3:
        raise AssertionError(
            f"dispatch policies are indistinguishable at n={head_n}: "
            f"{outcomes}")

    rows = [{"policy": pol, **head[pol]} for pol in POLICIES]
    print(table(rows, ["policy", "offered", "completed", "goodput",
                       "cold_starts", "shed", "p50_ms", "p99_ms",
                       "util_spread"],
                title=f"fleet n={head_n} (azure arrivals, seed {SEED}): "
                      f"dispatch policy comparison"))
    print()
    srows = [{"pattern": pat, "n": n, "policy": pol,
              "goodput": matrix[pat][str(n)][pol]["goodput"],
              "p99_ms": matrix[pat][str(n)][pol]["p99_ms"]}
             for pat in patterns for n in sizes for pol in POLICIES]
    print(table(srows, ["pattern", "n", "policy", "goodput", "p99_ms"],
                title=f"full matrix: {len(POLICIES)} policies x "
                      f"{len(sizes)} fleet sizes x {len(patterns)} "
                      f"patterns ({len(jobs)} cells, {wall:.0f}s on "
                      f"{workers} workers)"))

    payload = {"matrix": matrix, "distinct": distinct,
               "wall_s": round(wall, 1), "workers": workers,
               "config": {"seed": SEED, "duration_s": duration,
                          "warmup_s": warmup, "sizes": list(sizes),
                          "patterns": list(patterns),
                          "policies": list(POLICIES),
                          "fns_per_node": FNS_PER_NODE,
                          "mean_rate": MEAN_RATE}}
    save_json("cluster", payload)
    return payload


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    run(quick=ap.parse_args().quick)
