"""Run one cell of the chip benchmark once, and print its result line.

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``configs/<name>.json``) and a traffic mix (``traffic/<name>.json``);
its limits are in ``limits/<cell>.json`` and each of its metrics is read
by ``metrics/<metric>.py``. The configuration names its architecture by
its ``reference`` key: the program's config, the weights' leaves, the
operation count and the plain reference come from ``arch/<arch>.py``
and ``reference/<arch>.py`` (`architectures`). All are found by name:
adding a cell, a mix, a metric or an architecture adds files and entries
and edits none.

One run:

1. refuses to go on without the configuration's architecture, the
   program's code, a TPU, enough chips and a ``device_kind`` in
   ``peaks.json``;
2. set-up: makes the weights on the device from the seed
   (`reference.weights`), serializes them with the program's codec and
   stages them in an ``ObjectStore`` with the first input; deploys the
   cell's function on a ``nexus`` ``WorkerNode`` (``byte_scale`` 1.0, a
   SharedCache with room for one invocation's payloads), fills that
   cache with the weights (`_hold_weights`) and invokes the function
   once, which compiles the cell's shapes; ``setup_s`` ends at the
   first timed submission;
3. the window: one closed-loop client submits invocations for
   ``--seconds`` seconds, each with a new input staged before it, reads
   every durable output back from the store once its response has
   arrived, and with ``--trace 1`` records a profiler trace of the
   window;
4. the check: after the node is shut down, the architecture's reference
   recomputes a sample of the window's answers, drawn from the seed, and
   `compare` holds each number to the cell's limit.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the end-to-end metrics, or with
``--trace 1`` the per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each compared number beside its
limit. The same numbers end standard error. Earlier lines, and
``results/chipbench/<cell>-<seed>.json``, hold each invocation's latency
and phase breakdown and the compile counts.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import resource
import sys
import time

import numpy as np

from chipbench import architectures

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

#: deadline for a cold invocation's ingress prefetch of the weights,
#: which the SharedCache serves with host copies of 6.30 GB
CONNECT_TIMEOUT_S = 300.0
#: deadline for any one wait of an invocation's plan walk
PLAN_STALL_TIMEOUT_S = 400.0

#: the served step's program, as the profiler's ``XLA Modules`` line
#: names it: ``serving.bundle``'s ``jax.jit(model.prefill)``
STEP_PROGRAM = "jit_prefill"

_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_WRITE = "/jax/compilation_cache/cache_misses"


class Refused(Exception):
    """The run cannot be made here; ``code`` is the exit code."""

    def __init__(self, code: int, msg: str):
        super().__init__(msg)
        self.code = code


class CompileLog:
    """Counts, while open, the executables JAX builds (compiled or
    loaded from the persistent cache) and the persistent cache's hits
    and writes."""

    def __init__(self):
        self.built = self.hits = self.writes = 0
        self.build_s = 0.0

    def _event(self, event, **_kw):
        if event == _CACHE_HIT:
            self.hits += 1
        elif event == _CACHE_WRITE:
            self.writes += 1

    def _duration(self, event, secs, **_kw):
        if event == _COMPILE:
            self.built += 1
            self.build_s += secs

    def counts(self) -> dict:
        return {"built": self.built, "build_s": self.build_s,
                "cache_hits": self.hits, "cache_writes": self.writes}

    def __enter__(self):
        import jax
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_listener(self._event)
        jax.monitoring.unregister_event_duration_listener(self._duration)


# ------------------------------------------------------------- the cell

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    spec: dict              # configs/<config>.json
    mix: dict               # traffic/<traffic>.json
    limits: dict            # limits/<cell>.json: number -> {"limit", ...}
    metrics: list           # BENCHMARK.json entries this cell reports
    peaks: dict             # peaks.json
    arch: architectures.Architecture    # the one spec["reference"] names


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> Cell:
    """Cell `name` of ``<root>/BENCHMARK.json``, with its configuration's
    architecture found under ``<root>/chipbench``."""
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(2, f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name] if m["moves"] in
                                  reported else [])]
    for m in e2e:
        m["trace"] = 0
    for m in per_layer:
        m["trace"] = 1
    spec = _json(os.path.join(root, conf["file"]))
    try:
        arch = architectures.find(spec.get("reference"),
                                  os.path.join(root, "chipbench"))
    except architectures.Unknown as e:
        raise Refused(2, f"{conf['name']}: {e}")
    return Cell(name=name, chips=w["chips"], spec=spec,
                mix=_json(os.path.join(HERE, "traffic",
                                       f"{w['traffic']}.json")),
                limits=_json(os.path.join(HERE, "limits", f"{name}.json")),
                metrics=e2e + per_layer,
                peaks=_json(os.path.join(HERE, "peaks.json")), arch=arch)


def import_program(root: str = ROOT) -> None:
    src = os.path.join(root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    try:
        import repro.core.runtime  # noqa: F401
        import repro.models.serving  # noqa: F401
    except ImportError as e:
        raise Refused(2, f"cannot import the program under test: {e}")


def check_device(chips: int, peaks: dict) -> dict:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(3, f"needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise Refused(3, f"needs {chips} chips, JAX found {len(devs)}")
    kind = devs[0].device_kind
    if kind not in peaks["devices"]:
        raise Refused(3, f"no peaks for device kind {kind!r} in peaks.json")
    return {"platform": devs[0].platform, "kind": kind, "count": len(devs)}


def program_config(spec: dict, arch=None):
    """The program's `ModelConfig` for a configuration file, as `arch`
    (by default the one the file names) builds it."""
    return architectures.of(spec, arch).arch.program_config(spec)


def program_tree(flat: dict, struct):
    """The program's params tree from the benchmark's named leaves;
    refuses any difference in structure, shape or dtype."""
    import jax
    tree: dict = {}
    for name, arr in flat.items():
        *path, leaf = name.split(".")
        node = tree
        for p in path:
            node = node.setdefault(p, {})
        node[leaf] = arr
    want = jax.tree_util.tree_structure(struct)
    got = jax.tree_util.tree_structure(tree)
    if got != want:
        raise ValueError(f"weights tree {got} is not the program's {want}")
    for a, s in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(struct)):
        if a.shape != s.shape or a.dtype != s.dtype:
            raise ValueError(f"weights leaf {a.shape} {a.dtype} is not "
                             f"the program's {s.shape} {s.dtype}")
    return tree


# ------------------------------------------------------------ serving

def _span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def _invoke(node, w, keys, store, mix, gen, i: int, serialize) -> dict:
    """Stage invocation `i`'s input, invoke, wait, read the output back."""
    with _span("chipbench.stage"):
        store.put("in", keys[1], serialize.dumps(gen.tokens(i)))
    t = time.monotonic()
    rec = {"index": i, "t_submit": t}
    try:
        with _span("chipbench.invoke"):
            res = node.invoke(w.name).result(timeout=2 * PLAN_STALL_TIMEOUT_S)
    except Exception as e:                      # noqa: BLE001 — recorded
        rec.update(latency_s=time.monotonic() - t, ok=False, error=repr(e))
        return rec
    rec.update(latency_s=time.monotonic() - t, node_latency_s=res.latency_s,
               cold=res.cold, breakdown=res.breakdown,
               status=(res.response or {}).get("statusCode"))
    with _span("chipbench.readback"):
        key = f"{res.invocation_id}-out"
        try:
            body = store.get("out", key)
        except Exception as e:                  # noqa: BLE001 — recorded
            body, rec["error"] = None, repr(e)
        store.delete("out", key)
    rec["body"] = body
    cold_mix = mix["instances"] == "scale_to_zero"
    rec["ok"] = (rec["status"] == 200 and body is not None
                 and len(body) == w.profile.puts[0].size_bytes
                 and (res.cold or not cold_mix))
    if not rec["ok"] and cold_mix and not res.cold:
        rec["error"] = "a scale-to-zero invocation came back warm"
    if cold_mix:
        # the threaded node keeps no keep-alive clock: drop the idle
        # instance through the pool the node keeps per function
        node._pools[w.name].scale_down(keep=0)
    return rec


def _hold_weights(node, w, store, key: str) -> None:
    """Fill the node's SharedCache with the staged weights object, as
    the miss path fills it (content hash, arena copy, the store's etag),
    without the modeled storage transfer: the cells measure a node that
    already holds the model, and no timed invocation takes a miss."""
    data, meta = store.get_with_meta("in", key)
    if not node.cache_plane.fill(w.name, "in", key, data, len(data),
                                 hinted=True, etag=meta.etag):
        raise RuntimeError("the SharedCache did not admit the weights")


def serve(cell: Cell, seed: int, seconds: float, trace: bool, t0: float,
          log=print) -> dict:
    """Set-up and the measured window; returns what the metrics and the
    check read."""
    import jax
    from repro.core.cache import CacheSpec
    from repro.core.runtime import WorkerNode
    from repro.core.storage import ObjectStore
    from repro.core.workloads import MB, ml_suite_at
    from repro.models import serialize, serving

    from chipbench import loadgen
    from chipbench.reference import weights

    mix = cell.mix
    cfg = program_config(cell.spec, cell.arch)
    w = ml_suite_at({mix["role"]: cfg})[mix["scenario"]]
    structs = serving.bundle(cfg)["structs"]
    gen = loadgen.Mix(mix, cell.spec, seed)
    if structs[mix["input"]].shape != gen.shape:
        raise ValueError(f"{mix['scenario']} takes {mix['input']} "
                         f"{structs[mix['input']].shape}, the mix sends "
                         f"{gen.shape}")
    with CompileLog() as clog:
        params = program_tree(weights.make(cell.spec, seed, cell.arch),
                              structs["params"])
        blob = serialize.dumps(params)
        del params
        store = ObjectStore()
        sizes = [op.size_bytes for op in (*w.profile.gets, *w.profile.puts)]
        node = WorkerNode("nexus", store=store, byte_scale=1.0,
                          cache=CacheSpec(capacity_mb=sum(sizes) / MB),
                          connect_timeout_s=CONNECT_TIMEOUT_S,
                          plan_stall_timeout_s=PLAN_STALL_TIMEOUT_S)
        try:
            node.deploy(w)
            keys = node.seed_input(
                w.name, payloads=[blob, serialize.dumps(gen.tokens(0))])
            del blob
            _hold_weights(node, w, store, keys[0])
            warm = _invoke(node, w, keys, store, mix, gen, 0, serialize)
            if not warm["ok"]:
                raise RuntimeError(f"warm-up invocation failed: "
                                   f"{warm.get('error', warm.get('status'))}")
            setup = clog.counts()
            setup_s = time.monotonic() - t0
            trace_dir = None
            if trace:
                trace_dir = os.path.join(
                    ROOT, "results", "chipbench",
                    f"trace-{cell.name}-{seed}")
                jax.profiler.start_trace(trace_dir)
            ru0 = resource.getrusage(resource.RUSAGE_SELF)
            recs, i = [], 1
            with _span("chipbench.window"):
                t_open = time.monotonic()
                while time.monotonic() - t_open < seconds:
                    recs.append(_invoke(node, w, keys, store, mix, gen, i,
                                        serialize))
                    i += 1
                window_s = time.monotonic() - t_open
            ru1 = resource.getrusage(resource.RUSAGE_SELF)
            if trace:
                jax.profiler.stop_trace()
            window = clog.counts()
            stats = jax.devices()[0].memory_stats() or {}
            cache_stats = node.cache_stats()
        finally:
            node.shutdown()
    del node, store
    gc.collect()
    for r in recs:
        log(json.dumps({"invocation": r["index"],
                        "latency_s": r["latency_s"],
                        "cold": r.get("cold"), "ok": r["ok"],
                        "breakdown": r.get("breakdown")}))
    return {
        "setup_s": setup_s, "window_s": window_s,
        "cpu_s": (ru1.ru_utime - ru0.ru_utime) + (ru1.ru_stime - ru0.ru_stime),
        "invocations": recs, "warmup": {k: v for k, v in warm.items()
                                        if k != "body"},
        "compiles": {"setup": setup,
                     "window": {k: window[k] - setup[k] for k in setup}},
        "cache": cache_stats,
        "memory_peak_bytes": stats.get("peak_bytes_in_use"),
        "trace_dir": trace_dir, "gen": gen, "structs": structs,
    }


# -------------------------------------------------------------- check

def check(cell: Cell, seed: int, run: dict, control: bool = False) -> dict:
    """The compared numbers over a sample of the window's answers:
    ``{"program": {...}, "control": {...} (with `control`), "sample"}``.
    """
    import jax
    from repro.models import serialize

    from chipbench.reference import weights

    mix, gen = cell.mix, run["gen"]
    arch = cell.arch
    ok = [r for r in run["invocations"] if r["ok"]]
    sample = [ok[j] for j in gen.sample(len(ok))]
    want = (mix["compare"],)
    numbers = arch.numbers(mix["compare"])
    w = weights.make(cell.spec, seed, arch)
    pairs, ctl_pairs = [], []
    for r in sample:
        got = jax.tree.map(np.asarray, serialize.loads(
            run["structs"][mix["output"]], r["body"]))
        tokens = gen.tokens(r["index"])
        ref = arch.reference.forward(cell.spec, w, tokens, want=want)
        pairs.append(_pair(mix, got, ref))
        if control:
            low = arch.reference.forward(cell.spec, w, tokens, want=want,
                                         precision="float8_e4m3fn")
            ctl_pairs.append(_pair(mix, _as_served(low, tokens), ref))
    del w
    out = {"program": numbers(pairs) if pairs else {},
           "sample": [r["index"] for r in sample]}
    if control:
        out["control"] = numbers(ctl_pairs) if ctl_pairs else {}
    return out


def _as_served(out: dict, tokens) -> dict:
    """A reference's output in the form the program serves it: with the
    position counters of a cache that holds the whole prompt in order."""
    return dict(out, pos=np.full(tokens.shape[:1], tokens.shape[1]),
                slot_pos=np.broadcast_to(np.arange(tokens.shape[1]),
                                         tokens.shape))


def _pair(mix: dict, got, ref: dict):
    if mix["compare"] != "logits":
        return got, ref
    return (got["logits"] if isinstance(got, dict) else got), ref["logits"]


# ------------------------------------------------------------ metrics

def _reader(name: str):
    return architectures.load_module(
        os.path.join(HERE, "metrics", f"{name}.py"),
        f"chipbench_metric_{name.replace('.', '_')}").read


def read_metrics(cell: Cell, run: dict, trace: bool) -> dict:
    out = {}
    for m in cell.metrics:
        if m["trace"] != int(trace):
            continue
        value = _reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


# --------------------------------------------------------------- main

def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, t0: float,
             device: dict, log=print) -> dict:
    """One run of `cell`; returns the result line's object."""
    from chipbench import flops, trace_reduce
    run = serve(cell, seed, seconds, trace, t0, log=log)
    run["peaks"] = cell.peaks["devices"][device["kind"]]
    run["flops_per_step"] = flops.prefill_flops(
        cell.spec, cell.mix["batch"], cell.mix["seq"], cell.arch)["total"]
    run["step_program"] = STEP_PROGRAM
    run["trace"] = None
    dev = dict(device, memory_peak_bytes=run["memory_peak_bytes"])
    if trace:
        run["trace"] = _reduce_dir(run["trace_dir"], trace_reduce)
        fault = trace_fault(run["trace"], device["count"])
        if fault:
            _save(cell, seed, seconds, trace, run, None)
            raise Refused(4, f"traced run unreadable: {fault}")
        dev["busy_s"] = run["trace"]["busy_s"]
        dev["window_s"] = run["trace"]["window_s"]
    metrics = read_metrics(cell, run, trace)
    t = time.monotonic()
    numbers = check(cell, seed, run)["program"]
    run["check_s"] = time.monotonic() - t
    failed = sum(not r["ok"] for r in run["invocations"])
    checks = {n: {"value": v, "limit": cell.limits[n]["limit"]}
              for n, v in numbers.items()}
    correct = (failed == 0 and bool(checks) and
               all(c["value"] <= c["limit"] for c in checks.values()))
    result = {"correct": correct, "attempted": len(run["invocations"]),
              "failed": failed, "metrics": metrics, "device": dev}
    if run["trace"] and "device_ops" in run["trace"]:
        result["breakdown"] = {"device_ops": run["trace"]["device_ops"],
                               "idle_gaps": run["trace"]["idle_gaps"]}
    result["checks"] = checks
    _save(cell, seed, seconds, trace, run, result)
    return result


def trace_fault(t: dict, chips: int) -> str | None:
    """Why a reduced trace cannot give the device metrics, or None."""
    if not t.get("chips"):
        return f"no /device:TPU plane among {t.get('planes')}"
    if t["chips"] < chips:
        return f"{t['chips']} device planes for {chips} chips"
    if not t.get("busy_s"):
        return "no device operation inside the window"
    if STEP_PROGRAM not in t.get("modules", {}):
        return (f"no {STEP_PROGRAM} among the device's programs "
                f"{sorted(t.get('modules', {}))}")
    return None


def _reduce_dir(trace_dir: str, trace_reduce) -> dict:
    paths = [os.path.join(d, f) for d, _, fs in os.walk(trace_dir)
             for f in fs if f.endswith(".xplane.pb")]
    return trace_reduce.reduce(max(paths, key=os.path.getmtime)) \
        if paths else {}


def _save(cell, seed, seconds, trace, run, result) -> None:
    out = os.path.join(ROOT, "results", "chipbench")
    os.makedirs(out, exist_ok=True)
    keep = {k: v for k, v in run.items()
            if k not in ("invocations", "gen", "structs", "peaks")}
    keep["invocations"] = [{k: v for k, v in r.items() if k != "body"}
                           for r in run["invocations"]]
    with open(os.path.join(out, f"{cell.name}-{seed}.json"), "w") as f:
        json.dump({"cell": cell.name, "seed": seed, "seconds": seconds,
                   "trace": int(trace), "run": keep, "result": result},
                  f, indent=1, default=str)


def parse(argv) -> argparse.Namespace:
    p = argparse.ArgumentParser(prog="chipbench/run.py")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def start(argv) -> tuple:
    """Parse, load the cell, import the program and claim the chip:
    ``(args, cell, device)``."""
    args = parse(argv)
    cell = load_cell(args.workload)
    import_program()
    from repro.models import compile_cache
    compile_cache.enable()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    device = check_device(cell.chips, cell.peaks)
    return args, cell, device


def main(argv, t0: float) -> int:
    try:
        args, cell, device = start(argv)
        print(f"device: {json.dumps(device)}")
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          t0, device)
    except Refused as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return e.code
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}",
              file=sys.stderr)
    print(json.dumps(result))
    return 0
