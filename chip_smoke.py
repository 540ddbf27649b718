#!/usr/bin/env python3
"""Serve granite-8b at its published widths through `WorkerNode` on one TPU.

Run from the root of a checkout, on a machine with one TPU chip::

    python3 chip_smoke.py

The model is granite-8b with 9 of its 36 layers (`configs.granite_8b.CHIP`:
one chip's stage of a four-stage pipeline) and random weights from a
fixed seed. For each of LLM-COLD, LLM-PREFILL, LLM-DECODE and EMB it

* deploys the scenario on a ``nexus`` node (``byte_scale`` 1.0, with the
  SharedCache plane), stages the real serialized payloads in the object
  store and invokes it three times in a row: the first invocation is
  the cold one;
* invokes it once on a ``baseline`` node over the same object store;
* checks that every invocation succeeded, that the durable outputs are
  byte-identical across repeats and across the two variants, and that
  they equal a direct call of the same jitted steps on the same params,
  whose logits must be finite.

Earlier lines, and ``results/chip_smoke.json``, report per scenario the
latencies on the host clock (they include the modeled storage, SDK and
rate-limit time), the device kind, the process's peak device memory so
far and how many executables each invocation had to build; then the
compile cache's hits. The last line is one JSON object, printed only
when every check passed. Without a TPU the script exits non-zero before
doing any work.
"""
from __future__ import annotations

import gc
import hashlib
import json
import os
import sys
import time
import traceback

from chipbench.harness import CompileLog

HERE = os.path.dirname(os.path.abspath(__file__))

SCENARIOS = ("LLM-COLD", "LLM-PREFILL", "LLM-DECODE", "EMB")
INVOCATIONS = 3

#: a cold invocation's ingress prefetch of the 4.33 GB weights object:
#: the backend's 600 Mbit/s per-client rate limit holds it about 55 s,
#: the modeled SDK about 10 s, host copies and hashing the rest (77-79 s
#: measured on a TPU v5e host)
CONNECT_TIMEOUT_S = 150.0
#: a whole cold invocation: that fetch, a 0.6 GB KV GET and PUT, the
#: host-to-device copy of the weights and the compile inside the handler
#: (106 s measured for LLM-DECODE on a TPU v5e host, compile cache warm)
PLAN_STALL_TIMEOUT_S = 240.0


def _sha(data) -> str:
    return hashlib.sha256(data).hexdigest()


def _direct(name: str, payloads: list, cfg):
    """(durable output, logits) of the same jitted steps on the same
    params, called directly, outside the platform."""
    import numpy as np
    from repro.models import serialize, serving
    b = serving.bundle(cfg)
    st = b["structs"]
    if name == "LLM-COLD":
        params = serialize.loads(st["params"], b"".join(payloads[:-1]))
        tokens = serialize.loads(st["prompt"], payloads[-1])
        logits, cache = b["prefill"](params, {"tokens": tokens})
        logits, _ = b["decode"](params, cache, serving.next_token(logits))
        durable = serialize.dumps(logits)
    elif name == "LLM-PREFILL":
        params = serialize.loads(st["params"], payloads[0])
        tokens = serialize.loads(st["prompt"], payloads[1])
        logits, cache = b["prefill"](params, {"tokens": tokens})
        durable = serialize.dumps(cache)
    elif name == "LLM-DECODE":
        params = serialize.loads(st["params"], payloads[0])
        cache, token = serialize.loads(
            (st["decode_cache"], st["step_token"]), payloads[1])
        logits, cache = b["decode"](params, cache, token)
        durable = serialize.dumps(cache)
    elif name == "EMB":
        params = serialize.loads(st["params"], payloads[0])
        tokens = serialize.loads(st["enc_tokens"], payloads[1])
        logits, _ = b["prefill"](params, {"tokens": tokens})
        durable = serialize.dumps(logits)
    else:
        raise ValueError(name)
    return durable, np.asarray(logits)


def _invoke(system: str, w, store, payloads: list, n: int,
            log: CompileLog) -> list[dict]:
    """Deploy `w` on a fresh `system` node over `store`, stage the
    payloads, invoke `n` times serially; unstage them and shut the node
    down."""
    from repro.core.cache import CacheSpec
    from repro.core.runtime import WorkerNode
    from repro.core.workloads import MB
    cache = None
    if system != "baseline":
        # room for one invocation's payloads: a repeat hits its inputs,
        # and its write-allocated output evicts the previous repeat's
        nbytes = sum(op.size_bytes
                     for op in (*w.profile.gets, *w.profile.puts))
        cache = CacheSpec(capacity_mb=nbytes / MB)
    node = WorkerNode(system, store=store, byte_scale=1.0, cache=cache,
                      connect_timeout_s=CONNECT_TIMEOUT_S,
                      plan_stall_timeout_s=PLAN_STALL_TIMEOUT_S)
    runs, keys = [], []
    try:
        node.deploy(w)
        keys = node.seed_input(w.name, payloads=payloads)
        for _ in range(n):
            built = log.built
            res = node.invoke(w.name).result(
                timeout=2 * PLAN_STALL_TIMEOUT_S)
            outs = []
            for k in range(len(w.profile.puts)):
                key = f"{res.invocation_id}-out" + ("" if k == 0 else f"-{k}")
                body = node.store.get("out", key)
                outs.append((len(body), _sha(body)))
                node.store.delete("out", key)
            runs.append({"latency_s": res.latency_s, "cold": res.cold,
                         "built": log.built - built,
                         "breakdown": res.breakdown,
                         "response": res.response, "outs": outs})
    finally:
        for key in keys:
            store.delete("in", key)
        node.shutdown()
    return runs


def serve_scenario(w, store, log: CompileLog,
                   invocations: int = INVOCATIONS) -> dict:
    """One scenario end to end; returns its record, with ``failures``
    naming every check that did not hold."""
    import jax
    import numpy as np
    from repro.models import serving
    cfg = w.model
    wall = {}
    t0 = time.monotonic()
    payloads = serving.seed_payloads(w.name, cfg)
    wall["seed"] = time.monotonic() - t0
    nexus = _invoke("nexus", w, store, payloads, invocations, log)
    gc.collect()                # the node's arenas before the next node's
    wall["nexus"] = time.monotonic() - t0 - sum(wall.values())
    base = _invoke("baseline", w, store, payloads, 1, log)
    gc.collect()
    wall["baseline"] = time.monotonic() - t0 - sum(wall.values())
    durable, logits = _direct(w.name, payloads, cfg)
    del payloads
    wall["direct"] = time.monotonic() - t0 - sum(wall.values())

    declared = [p.size_bytes for p in w.profile.puts]
    expect = [(len(durable), _sha(durable))]
    failures = []
    for tag, run in [(f"nexus[{i}]", r) for i, r in enumerate(nexus)] + \
            [("baseline", base[0])]:
        if run["response"].get("statusCode") != 200:
            failures.append(f"{tag}: status {run['response']}")
        if [n for n, _ in run["outs"]] != declared:
            failures.append(f"{tag}: output sizes differ from the profile")
        if run["outs"] != expect:
            failures.append(f"{tag}: durable output differs from the "
                            f"direct call")
    if not np.isfinite(logits).all():
        failures.append("direct logits are not finite")
    if w.name == "LLM-DECODE":
        token = int(np.argmax(logits[:, -1], axis=-1)[0])
        if any(r["response"].get("token") != token for r in nexus + base):
            failures.append("decode token differs from the direct call")
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    return {
        "scenario": w.name, "model": cfg.name,
        "nexus_host_latency_s": [r["latency_s"] for r in nexus],
        "nexus_cold": [r["cold"] for r in nexus],
        "nexus_executables_built": [r["built"] for r in nexus],
        "baseline_host_latency_s": base[0]["latency_s"],
        "nexus_breakdown_s": [r["breakdown"] for r in nexus],
        "baseline_breakdown_s": base[0]["breakdown"],
        "phase_wall_s": wall,
        "durable": expect,
        "logits_shape": list(logits.shape),
        "device_kind": dev.device_kind,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "failures": failures,
    }


def serve_all(models: dict, log: CompileLog, emit=print) -> list[dict]:
    """Every scenario of `SCENARIOS` at `models` (role -> config), in
    turn, over one object store. A scenario that raises is recorded as
    failed and the next one still runs."""
    from repro.core.storage import ObjectStore
    from repro.core.workloads import ml_suite_at
    suite = ml_suite_at(models)
    store = ObjectStore()
    records = []
    for name in SCENARIOS:
        t0 = time.monotonic()
        try:
            rec = serve_scenario(suite[name], store, log)
        except Exception:                       # noqa: BLE001 — reported
            rec = {"scenario": name, "failures": [traceback.format_exc()]}
        rec["scenario_wall_s"] = time.monotonic() - t0
        gc.collect()
        emit(json.dumps(rec))
        records.append(rec)
    return records


def main() -> int:
    src = os.path.join(HERE, "src")
    sys.path.insert(0, src)
    try:
        from repro.configs.granite_8b import CHIP
        from repro.models import compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the repository's code: {e}",
              file=sys.stderr)
        return 2
    if not os.path.abspath(compile_cache.__file__).startswith(src + os.sep):
        print(f"chip_smoke: {compile_cache.__file__} is not this "
              f"checkout's code", file=sys.stderr)
        return 2
    cache_dir = compile_cache.enable()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 3
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {json.dumps(device)}")
    print(f"compile cache: {cache_dir}")
    t0 = time.monotonic()
    with CompileLog() as log:
        records = serve_all({"llm": CHIP, "emb": CHIP}, log)
    summary = {"compile_cache_dir": cache_dir,
               "executables_built": log.built,
               "build_s": log.build_s,
               "compile_cache_hits": log.hits,
               "compile_cache_writes": log.writes,
               "wall_s": time.monotonic() - t0}
    print(f"compile cache: {json.dumps(summary)}")
    out_dir = os.path.join(HERE, "results")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "chip_smoke.json"), "w") as f:
        json.dump({"device": device, "summary": summary,
                   "scenarios": records}, f, indent=1)
    failed = [r["scenario"] for r in records if r["failures"]]
    if failed:
        print(f"chip_smoke: failed scenarios: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
