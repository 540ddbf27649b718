"""Paper Fig. 6 — deployment density under the p99 < 5x-unloaded SLO.

Sweeps deployed-function count per system through the virtual-time
cluster simulator (same cost model as the threaded runtime; §6 setup:
4 worker nodes x 28 cores x 128 GB, 280-VM overcommit, Azure-like
arrivals) and reports the density knee plus CPU/memory utilization at
the baseline's largest sustainable scale (the paper's common operating
point comparison).

The PlanProgram DES (ISSUE 3) makes the previously-unaffordable *full
matrix* routine, so beyond the Fig 6 reproduction this bench now runs
all 7 system variants x multiple seeds x arrival patterns (Poisson,
Azure-like MMPP, heavy-burst, diurnal), each `find_density` search
binary-refined past the coarse step, fanned out over the machine's
cores. Results land in ``results/density.json``: the paper figure
under ``density``/``gains``/``operating_point`` (unchanged keys) and
the matrix under ``matrix``/``matrix_summary``.

The HotLoop PR (ISSUE 6) adds a validation lap: every matrix cell is
re-searched with ``find_density(fast=True)`` — the fluid mean-value
bracket (`repro.core.fluid`) plus the exact boundary walk — and the
returned densities must match the exact matrix cell-for-cell while
spending a fraction of the exact probes (``fast_path`` in the
payload: total and coarse-sweep probe ratios).
"""
from __future__ import annotations

import os
import time
from concurrent.futures import ProcessPoolExecutor

from repro.core.des import DensitySimulator, find_density
from repro.core.plan import SYSTEMS

from benchmarks.common import SPAWN, pct, save_json, table

# + nexus-prefetch-only: same fetch overlap as nexus-async but no early
# release — its density gap vs nexus-async isolates §4.2.5's VM-holding
# effect, a sweep the PhasePlan layer gives us for one spec entry.
SYSTEMS_ORDER = ("baseline", "nexus-tcp", "nexus-prefetch-only",
                 "nexus-async", "nexus")

#: the full matrix covers every variant, sdk-only and wasm included
ALL_SYSTEMS = tuple(SYSTEMS)

SEEDS = (1, 2, 3)


def _search(args) -> tuple[tuple, int, list]:
    (system, seed, pattern, duration, step, refine_to, fast) = args
    best, results = find_density(
        system, lo=160, hi=800, step=step, seed=seed,
        refine_to=refine_to, duration_s=duration, warmup_s=10.0,
        arrival_pattern=pattern, fast=fast)
    probes = [{"n": r.n_functions,
               "slowdown": round(r.geomean_slowdown(), 2),
               "cpu": round(r.cpu_util, 3), "mem": round(r.mem_util, 3),
               "cold": r.cold_starts, "pass": r.meets_slo()}
              for r in results]
    return (system, seed, pattern), best, probes


def run(quick: bool = False) -> dict:
    duration = 30.0 if quick else 60.0
    step = 40 if quick else 20
    refine_to = 8 if quick else 2
    patterns = ("azure", "poisson") if quick \
        else ("azure", "poisson", "bursty", "diurnal")

    # ------------------------- the full matrix: system x seed x pattern
    jobs = [(s, seed, pat, duration, step, refine_to, False)
            for s in ALL_SYSTEMS for seed in SEEDS for pat in patterns]
    workers = min(os.cpu_count() or 1, len(jobs))
    t0 = time.time()
    with ProcessPoolExecutor(max_workers=workers, mp_context=SPAWN) as pool:
        found = list(pool.map(_search, jobs))
    sweep_wall = time.time() - t0

    # ------- fluid-bracketed fast mode: same densities, fewer probes.
    # Every cell of the matrix re-searched with `fast=True`; the
    # returned densities must MATCH the exact matrix cell-for-cell.
    fjobs = [j[:-1] + (True,) for j in jobs]
    t0 = time.time()
    with ProcessPoolExecutor(max_workers=workers, mp_context=SPAWN) as pool:
        fast_found = list(pool.map(_search, fjobs))
    fast_wall = time.time() - t0

    matrix: dict[str, dict] = {}
    sweep: dict[str, list] = {}
    for (system, seed, pattern), best, probes in found:
        matrix.setdefault(pattern, {}).setdefault(system, {})[seed] = best
        if pattern == "azure" and seed == SEEDS[0]:
            sweep[system] = probes          # Fig 6a probe trajectories

    exact_by_key = {key: (best, probes) for key, best, probes in found}
    mismatches = []
    probes_exact = probes_fast = 0
    sweep_exact = sweep_fast = 0        # coarse/bracketing phase only
    for key, best, probes in fast_found:
        e_best, e_probes = exact_by_key[key]
        if best != e_best:
            mismatches.append({"key": list(key), "exact": e_best,
                               "fast": best})
        probes_exact += len(e_probes)
        probes_fast += len(probes)
        sweep_exact += sum(1 for p in e_probes if (p["n"] - 160) % step == 0)
        sweep_fast += sum(1 for p in probes if (p["n"] - 160) % step == 0)
    fast_path = {
        "searches": len(fjobs),
        "densities_match": not mismatches,
        "mismatches": mismatches,
        "probes_exact": probes_exact, "probes_fast": probes_fast,
        "probe_ratio": round(probes_exact / max(probes_fast, 1), 2),
        "sweep_probes_exact": sweep_exact, "sweep_probes_fast": sweep_fast,
        "sweep_probe_ratio": round(sweep_exact / max(sweep_fast, 1), 2),
        "fast_wall_s": round(fast_wall, 1),
    }

    summary = []
    for pattern in patterns:
        for system in ALL_SYSTEMS:
            ds = [matrix[pattern][system][seed] for seed in SEEDS]
            summary.append({
                "pattern": pattern, "system": system,
                "density_mean": round(sum(ds) / len(ds), 1),
                "density_min": min(ds), "density_max": max(ds)})

    # ------------------------------- Fig 6a: paper ordering, azure mix
    density = {s: round(sum(matrix["azure"][s][sd] for sd in SEEDS)
                        / len(SEEDS)) for s in ALL_SYSTEMS}
    rows = [{"system": s, "density": density[s],
             "gain_%": round((density[s] / max(density["baseline"], 1) - 1)
                             * 100, 1)}
            for s in SYSTEMS_ORDER]

    # common operating point: baseline's max sustainable n
    n0 = density["baseline"]
    op_rows = []
    for s in SYSTEMS_ORDER:
        r = DensitySimulator(s, n0, seed=SEEDS[0], duration_s=duration,
                             warmup_s=10.0).run()
        op_rows.append({"system": s, "n": n0,
                        "cpu_util": round(r.cpu_util, 3),
                        "mem_util": round(r.mem_util, 3)})
    base_cpu = op_rows[0]["cpu_util"]
    base_mem = op_rows[0]["mem_util"]
    for r in op_rows:
        r["cpu_saving_%"] = round(pct(r["cpu_util"], base_cpu), 1)
        r["mem_saving_%"] = round(pct(r["mem_util"], base_mem), 1)

    print(table(rows, ["system", "density", "gain_%"],
                title="Fig 6a: deployment density, azure arrivals, "
                      f"mean of seeds {SEEDS} "
                      "(paper: 320 / 380 / 380 / 440 -> +18%/+18%/+37%)"))
    print()
    print(table(op_rows, ["system", "n", "cpu_util", "cpu_saving_%",
                          "mem_util", "mem_saving_%"],
                title=f"Fig 6b/6c at the common operating point n={n0} "
                      "(paper @180: CPU -35/-36/-44%, mem -36/-40/-31%)"))
    print()
    print(table(summary, ["pattern", "system", "density_mean",
                          "density_min", "density_max"],
                title=f"full matrix: {len(ALL_SYSTEMS)} variants x "
                      f"{len(SEEDS)} seeds x {len(patterns)} patterns "
                      f"({len(jobs)} density searches, "
                      f"{sweep_wall:.0f}s on {workers} workers)"))
    print()
    print(f"fluid fast path: {fast_path['searches']} searches re-run "
          f"fast=True — densities "
          f"{'all match' if fast_path['densities_match'] else 'MISMATCH'}; "
          f"probes {probes_exact} -> {probes_fast} "
          f"({fast_path['probe_ratio']}x total, "
          f"{fast_path['sweep_probe_ratio']}x on the coarse sweep), "
          f"{fast_wall:.0f}s")

    payload = {"density": density, "gains": rows, "sweep": sweep,
               "operating_point": op_rows,
               "matrix": matrix, "matrix_summary": summary,
               "fast_path": fast_path,
               "sweep_wall_s": round(sweep_wall, 1),
               "workers": workers,
               "config": {"duration_s": duration, "step": step,
                          "refine_to": refine_to, "seeds": list(SEEDS),
                          "patterns": list(patterns)}}
    save_json("density", payload)
    return payload


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    run(quick=ap.parse_args().quick)
