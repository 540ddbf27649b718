import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes, with zero device allocation (ShapeDtypeStruct inputs).

The two lines above MUST precede any jax import (jax locks the device
count on first init). Do not replicate them in conftest/pyproject —
tests and benches must see the real single CPU device.

Per cell this driver:
  1. builds the jitted step (train_step / prefill / serve_step) with the
     production in/out shardings,
  2. ``.lower(**input_specs).compile()`` against the requested mesh,
  3. records ``memory_analysis()`` (fits-HBM proof), ``cost_analysis()``
     (FLOPs/bytes for the roofline), and the collective-op byte sums
     parsed from the optimized HLO (the roofline's third term).

Usage:
  python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
  python -m repro.launch.dryrun --arch llama3-8b --shape decode_32k --multi-pod
  python -m repro.launch.dryrun --all --out results/dryrun.jsonl
(--all spawns one subprocess per cell: compile arenas are freed between
cells, and one cell's failure cannot poison the rest.)
"""
import argparse
import json
import re
import subprocess
import sys
import time

import jax

from repro.configs.base import SHAPES, all_cells, cell_is_runnable
from repro.configs import registry
from repro.launch import sharding as SH
from repro.launch import specs as SP
from repro.launch.mesh import make_production_mesh
from repro.models import get_model
from repro.optim import adamw_init, make_train_step

# ------------------------------------------------------- HLO collective scan

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Per-device bytes moved by each collective kind, from optimized HLO.

    Cost conventions (ring algorithms, per participating device):
      all-reduce: 2x result bytes; all-gather / all-to-all /
      collective-permute: result bytes; reduce-scatter: operand bytes
      (approximated by result x group size via the lhs when operands are
      unparsable — kept simple and stated in EXPERIMENTS.md).
    """
    out = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        if s.startswith("%") or s.startswith("ROOT"):
            m = re.search(r"=\s*((?:\([^)]*\))|(?:\w+\[[\d,]*\]\S*))\s+([\w-]+)",
                          s)
            if not m:
                continue
            op = m.group(2)
            if op not in _COLLECTIVES and not any(
                    op.startswith(c) for c in _COLLECTIVES):
                continue
            kind = next(c for c in _COLLECTIVES if op.startswith(c))
            shapes = _SHAPE_RE.findall(m.group(1))
            nbytes = sum(_shape_bytes(d, dims) for d, dims in shapes
                         if d in _DTYPE_BYTES)
            if kind == "all-reduce":
                nbytes *= 2
            out[kind] += nbytes
    return out


# ------------------------------------------------------------- cell lowering

def build_lowered(arch: str, shape_name: str, multi_pod: bool,
                  extra: dict | None = None):
    """Lower one cell; returns (lowered, mesh, kind)."""
    cfg = registry.get(arch)
    if extra:
        cfg = cfg.replace(**extra)
    shape = SHAPES[shape_name]
    ok, why = cell_is_runnable(cfg, shape)
    if not ok:
        raise SystemExit(f"SKIP {arch} x {shape_name}: {why}")

    mesh = make_production_mesh(multi_pod=multi_pod)
    model = get_model(cfg)
    pshapes = SP.param_specs(model)
    pshard = SH.param_shardings(pshapes, mesh)

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            batch = SP.train_batch_specs(cfg, shape)
            bshard = SH.batch_shardings(batch, mesh)
            state_shapes = jax.eval_shape(adamw_init, pshapes)
            sshard = SH.state_shardings(state_shapes, mesh)
            step = make_train_step(model)
            jitted = jax.jit(step, in_shardings=(sshard, bshard),
                             out_shardings=(sshard, None),
                             donate_argnums=(0,))
            lowered = jitted.lower(state_shapes, batch)
            return lowered, mesh, "train_step"

        if shape.kind == "prefill":
            batch = SP.prefill_batch_specs(cfg, shape)
            bshard = SH.batch_shardings(batch, mesh)
            logit_shapes, cache_shapes = jax.eval_shape(
                lambda p, b: model.prefill(p, b), pshapes, batch)
            cshard = SH.cache_shardings(cache_shapes, mesh,
                                        kind="prefill")
            jitted = jax.jit(
                lambda p, b: model.prefill(p, b),
                in_shardings=(pshard, bshard),
                out_shardings=(SH.logits_sharding(mesh, logit_shapes.shape),
                               cshard))
            lowered = jitted.lower(pshapes, batch)
            return lowered, mesh, "prefill_step"

        # decode: one new token against a seq_len-deep cache
        cache_shapes = SP.decode_cache_specs(cfg, shape)
        cshard = SH.cache_shardings(cache_shapes, mesh)
        token = SP.decode_token_spec(cfg, shape)
        tshard = SH.batch_shardings({"t": token}, mesh)["t"]
        logit_shapes, _ = jax.eval_shape(
            lambda p, c, t: model.decode_step(p, c, t),
            pshapes, cache_shapes, token)
        jitted = jax.jit(
            lambda p, c, t: model.decode_step(p, c, t),
            in_shardings=(pshard, cshard, tshard),
            out_shardings=(SH.logits_sharding(mesh, logit_shapes.shape),
                           cshard),
            donate_argnums=(1,))
        lowered = jitted.lower(pshapes, cache_shapes, token)
        return lowered, mesh, "serve_step"


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             extra: dict | None = None, hlo_out: str | None = None,
             analyze: bool = False) -> dict:
    t0 = time.time()
    lowered, mesh, kind = build_lowered(arch, shape_name, multi_pod, extra)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    analysis = None
    if analyze:
        # loop-corrected flops/bytes/collectives (XLA cost_analysis counts
        # while bodies once; see benchmarks/hlo_analysis.py)
        from benchmarks.hlo_analysis import analyze as hlo_analyze
        analysis = hlo_analyze(hlo)
    if hlo_out:
        with open(hlo_out, "w") as f:
            f.write(hlo)

    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "step": kind,
        "devices": mesh.devices.size,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "flops_per_device": cost.get("flops", -1.0),
        "bytes_accessed_per_device": cost.get("bytes accessed", -1.0),
        "collective_bytes_per_device": coll,
        "memory": {
            "argument_B": getattr(mem, "argument_size_in_bytes", -1),
            "output_B": getattr(mem, "output_size_in_bytes", -1),
            "temp_B": getattr(mem, "temp_size_in_bytes", -1),
            "code_B": getattr(mem, "generated_code_size_in_bytes", -1),
        },
    }
    if analysis is not None:
        rec["hlo_analysis"] = analysis
    if extra:
        rec["extra"] = extra
    return rec


# --------------------------------------------------------------------- main

def _cells_to_run() -> list[tuple[str, str, bool]]:
    cells = []
    for arch, shape_name in all_cells():
        cfg = registry.get(arch)
        ok, _ = cell_is_runnable(cfg, SHAPES[shape_name])
        if not ok:
            continue
        for multi_pod in (False, True):
            cells.append((arch, shape_name, multi_pod))
    return cells


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run every runnable cell x both meshes in "
                         "subprocesses, appending JSONL to --out")
    ap.add_argument("--out", default="results/dryrun.jsonl")
    ap.add_argument("--hlo-out", default=None,
                    help="also dump optimized HLO text to this path")
    ap.add_argument("--extra", default=None,
                    help="JSON dict of ModelConfig overrides (perf exps)")
    ap.add_argument("--analyze", action="store_true",
                    help="embed loop-corrected HLO flops/bytes/collectives")
    ap.add_argument("--single-pod-only", action="store_true",
                    help="--all: skip the 2x16x16 mesh (roofline table "
                         "is single-pod)")
    args = ap.parse_args()

    if args.all:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        done = set()
        if os.path.exists(args.out):
            with open(args.out) as f:
                for line in f:
                    r = json.loads(line)
                    if "error" not in r:
                        done.add((r["arch"], r["shape"], r["mesh"]))
        cells = _cells_to_run()
        for i, (arch, shape_name, multi_pod) in enumerate(cells):
            mesh_name = "2x16x16" if multi_pod else "16x16"
            if (arch, shape_name, mesh_name) in done:
                print(f"[{i+1}/{len(cells)}] skip (done) "
                      f"{arch} {shape_name} {mesh_name}", flush=True)
                continue
            if multi_pod and args.single_pod_only:
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape_name, "--out", args.out]
            if multi_pod:
                cmd.append("--multi-pod")
            if args.analyze:
                cmd.append("--analyze")
            if args.extra:
                cmd += ["--extra", args.extra]
            print(f"[{i+1}/{len(cells)}] {arch} {shape_name} {mesh_name}",
                  flush=True)
            r = subprocess.run(cmd, capture_output=True, text=True)
            if r.returncode != 0:
                err = (r.stderr or r.stdout).strip().splitlines()
                with open(args.out, "a") as f:
                    f.write(json.dumps({
                        "arch": arch, "shape": shape_name, "mesh": mesh_name,
                        "error": err[-3:] if err else "unknown"}) + "\n")
                print(f"    FAILED: {err[-1] if err else '?'}", flush=True)
        return

    assert args.arch and args.shape, "--arch/--shape or --all required"
    extra = json.loads(args.extra) if args.extra else None
    rec = run_cell(args.arch, args.shape, args.multi_pod, extra,
                   args.hlo_out, analyze=args.analyze)
    line = json.dumps(rec)
    print(line)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "a") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
