"""Calibrated ML compute costs: model analysis -> PhasePlan durations.

The DES and the SLO denominators price a `ComputeSegment` in Mcycles at
the paper's 2.1 GHz. For the synthetic suite those budgets are part of
the workload *definition*; for the MLServe suite they must come from
the models themselves, or the density/latency tables are fiction. This
module derives them:

    repro.models.flops (analytic FLOPs/HBM-bytes per arch x serving
    shape, the same machinery the roofline/hlo_analysis benches
    validate against jax ``cost_analysis`` and parsed optimized HLO)
        -> `MachineProfile` roofline  time = max(flops/peak, bytes/bw)
        -> Mcycles at `fabric.GHZ_MCYC_PER_S`  (the DES cycle currency)

and persists the result to the **committed** ``calibration.json`` next
to this module, so `workloads.ml_suite()` is pure data (no jax import,
no tracing) and every DES run prices the same calibrated costs — CI
cannot drift because a dependency re-traced a model differently.

Two scales are calibrated from one code path:

* ``full`` — the published configs on an HBM accelerator slice
  (`MACHINES['full']`): what the density simulator deploys;
* ``tiny`` — the SMOKE configs on a CPU-class profile
  (`MACHINES['tiny']`): what the threaded runtime actually *executes*
  inside handlers, with real tensors round-tripped through
  ``ctx.storage``. Sizes at this scale are exact serialized byte
  counts (`models.serialize.tree_nbytes` over ``jax.eval_shape``), so
  the declared `IOProfile` matches the handler's observed I/O to the
  byte.

Regeneration (``python -m repro.core.calibrate --write``) is
deterministic: pure shape/flop arithmetic, no RNG, no timestamps — the
acceptance test regenerates it and diffs against the committed file.
"""
from __future__ import annotations

import json
import os
from dataclasses import asdict, dataclass

from repro.core import fabric as F

#: committed calibration database (regenerate with --write)
CALIBRATION_PATH = os.path.join(os.path.dirname(__file__),
                                "calibration.json")

CALIBRATION_VERSION = 2

#: MLServe model roles -> registry arch ids. `full` uses the published
#: CONFIG, `tiny` the same module's SMOKE config.
ML_ROLES = {
    "llm": "llama3-8b",            # dense GQA decoder: prefill + decode
    "moe": "qwen3-moe-30b-a3b",    # expert-shard fan-in
    "emb": "granite-8b",           # batch encode
}

#: (batch, seq_len) per calibrated phase, per scale. `tiny` shapes are
#: what the threaded handlers really run; `full` are serving-realistic.
SERVING_SHAPES: dict[str, dict[str, tuple[int, int]]] = {
    "full": {"prefill": (1, 2048), "decode": (8, 2048),
             "encode": (32, 512)},
    "tiny": {"prefill": (1, 32), "decode": (1, 32), "encode": (4, 16)},
}

#: how many objects a role's weights are sharded into (LLM-COLD
#: fetches `LLM_WEIGHT_SHARDS` GETs, MOE fans in `MOE_SHARDS`: one
#: backbone + top-k expert shards). Roles absent here (emb) do not
#: shard and get no `weights_shard_bytes` entry.
ROLE_SHARDS = {"llm": 4, "moe": 3}
LLM_WEIGHT_SHARDS = ROLE_SHARDS["llm"]
MOE_SHARDS = ROLE_SHARDS["moe"]

SCALES = tuple(SERVING_SHAPES)
PHASES = ("prefill", "decode", "encode")


def serving_shapes(cfg) -> dict[str, tuple[int, int]]:
    """The serving shapes a config runs at: `tiny` for SMOKE configs,
    `full` for every config at published widths."""
    return SERVING_SHAPES["tiny" if cfg.name.endswith("-smoke") else "full"]


@dataclass(frozen=True)
class MachineProfile:
    """The serving substrate a calibration targets, as pure data.

    ``mcycles(flops, hbm_bytes)`` is a two-term roofline: compute time
    at ``mfu`` x dense peak vs HBM-streaming time, whichever binds,
    expressed in the DES's Mcycle currency (2.1 GHz host cycles) so a
    calibrated `ComputeSegment` drops into the existing cost model
    unchanged.
    """

    name: str
    peak_tflops: float              # dense bf16 peak, per device
    hbm_gbps: float                 # HBM bandwidth, per device
    mfu: float = 0.45               # achieved fraction of peak
    devices: int = 1                # serving-slice size (shards weights)
    ghz_mcyc_per_s: float = F.GHZ_MCYC_PER_S

    def seconds(self, flops: float, hbm_bytes: float) -> float:
        compute = flops / (self.peak_tflops * 1e12 * self.mfu)
        memory = hbm_bytes / (self.hbm_gbps * 1e9)
        return max(compute, memory)

    def mcycles(self, flops: float, hbm_bytes: float) -> float:
        return self.seconds(flops, hbm_bytes) * self.ghz_mcyc_per_s


MACHINES: dict[str, MachineProfile] = {
    # 8-device HBM accelerator slice (A100/TPUv4-class per-device specs)
    "full": MachineProfile("hbm-accel-8x", peak_tflops=275.0,
                           hbm_gbps=1200.0, mfu=0.45, devices=8),
    # one CPU core running the SMOKE configs (what handlers execute)
    "tiny": MachineProfile("cpu-smoke", peak_tflops=0.005, hbm_gbps=8.0,
                           mfu=1.0, devices=1),
}


def shard_bytes(total: int, shards: int) -> list[int]:
    """Deterministic near-even split of `total` bytes into `shards`
    contiguous chunks (every chunk non-empty; sizes sum exactly)."""
    if total < shards:
        raise ValueError(f"cannot split {total}B into {shards} shards")
    base, rem = divmod(total, shards)
    return [base + (1 if i < rem else 0) for i in range(shards)]


# ---------------------------------------------------------------- derivation

#: one TPU v5e chip at its published peaks (Google Cloud documentation,
#: "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM) with ``mfu=1.0``: the
#: roofline lower bound, for suites that execute at chip size
#: (`workloads.ml_suite_at`). Not part of the committed calibration.
V5E = MachineProfile("tpu-v5e", peak_tflops=197.0, hbm_gbps=819.0,
                     mfu=1.0, devices=1)


def role_entry(role: str, cfg, machine: MachineProfile) -> dict:
    """One calibration entry: `cfg` serving `role` on `machine`.
    Imports jax + the analytic FLOPs machinery lazily: only
    regeneration and chip-size suites pay for it — consumers of the
    committed file read the JSON."""
    from repro.configs.base import InputShape
    from repro.models import serving
    from repro.models.flops import hbm_bytes_ideal, model_flops

    shapes = serving_shapes(cfg)
    phases = {}
    for phase in PHASES:
        B, S = shapes[phase]
        kind = "decode" if phase == "decode" else "prefill"
        ishape = InputShape(f"serve_{phase}", S, B, kind)
        flops = model_flops(cfg, ishape)["total"] / machine.devices
        hbm = hbm_bytes_ideal(cfg, ishape, devices=machine.devices)
        phases[phase] = {
            "batch": B, "seq_len": S,
            "flops_per_device": round(flops, 3),
            "hbm_bytes_per_device": round(hbm, 3),
            "seconds": round(machine.seconds(flops, hbm), 9),
            "mcycles": round(machine.mcycles(flops, hbm), 6),
        }

    # exact serialized byte sizes, per device. At tiny scale these ARE
    # the handler's observed I/O sizes; at full scale the same shape
    # arithmetic over the published config, sharded across the slice.
    sizes = serving.role_sizes(cfg, devices=machine.devices)
    entry = {"arch": cfg.name, "family": cfg.family, **sizes,
             "phases": phases}
    if role in ROLE_SHARDS:
        entry["weights_shard_bytes"] = shard_bytes(
            entry["params_bytes"], ROLE_SHARDS[role])
    return entry


def _derive_role(scale: str, role: str) -> dict:
    """One (scale, role) entry of the committed database."""
    from repro.configs import registry
    arch = ML_ROLES[role]
    cfg = registry.get(arch) if scale == "full" else registry.get_smoke(arch)
    return role_entry(role, cfg, MACHINES[scale])


def derive_calibration() -> dict:
    """Recompute the whole calibration database (both scales). Pure
    arithmetic over configs — bit-identical on every invocation."""
    return {
        "version": CALIBRATION_VERSION,
        "ghz_mcyc_per_s": F.GHZ_MCYC_PER_S,
        "machines": {s: asdict(m) for s, m in MACHINES.items()},
        "serving_shapes": {s: {p: list(bs) for p, bs in sh.items()}
                           for s, sh in SERVING_SHAPES.items()},
        "models": {f"{scale}/{role}": _derive_role(scale, role)
                   for scale in SCALES for role in ML_ROLES},
    }


# ------------------------------------------------------------------- access

_cache: dict | None = None


def load_calibration(path: str | None = None) -> dict:
    """The committed calibration database (cached). No jax, no tracing:
    `workloads.ml_suite()` and the DES stay pure-data consumers."""
    global _cache
    if path is None:
        if _cache is None:
            with open(CALIBRATION_PATH) as f:
                _cache = json.load(f)
        return _cache
    with open(path) as f:
        return json.load(f)


def model_entry(scale: str, role: str, cal: dict | None = None) -> dict:
    cal = cal if cal is not None else load_calibration()
    try:
        return cal["models"][f"{scale}/{role}"]
    except KeyError:
        raise KeyError(
            f"no calibration for {scale}/{role} — regenerate with "
            f"`python -m repro.core.calibrate --write`") from None


def dump_calibration(cal: dict, path: str | None = None) -> str:
    path = path or CALIBRATION_PATH
    with open(path, "w") as f:
        json.dump(cal, f, indent=1, sort_keys=True)
        f.write("\n")
    return path


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--write", action="store_true",
                    help="rewrite the committed calibration.json")
    ap.add_argument("--check", action="store_true",
                    help="fail unless the committed file regenerates "
                         "bit-identically")
    args = ap.parse_args()
    cal = derive_calibration()
    if args.write:
        print(f"wrote {dump_calibration(cal)}")
        return
    committed = load_calibration()
    same = committed == cal
    print(json.dumps({k: v for k, v in cal.items() if k != "models"},
                     indent=1, sort_keys=True))
    print(f"models calibrated: {sorted(cal['models'])}")
    print(f"matches committed calibration.json: {same}")
    if args.check and not same:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
