"""MLServe model plumbing: shape structs, seed payloads, handler cores.

Two consumers share this module so the declared and the executed can
never drift:

* `core.calibrate` calls `role_sizes` (pure ``jax.eval_shape``
  arithmetic) when regenerating ``calibration.json`` — the byte sizes
  the `IOProfile`s declare;
* the MLServe handlers in `core.workloads` call the ``llm_*`` /
  ``emb_*`` / ``moe_*`` cores at the config their deployment names
  (`Workload.model`; the role's SMOKE config by default): real
  forwards over real tensors decoded from the bytes ``ctx.storage``
  handed them, re-encoded with the same deterministic codec
  (`models.serialize`) before the PUT.

Everything here is deterministic: params from a fixed PRNGKey, prompts
from a fixed arithmetic progression, the codec headerless and
canonical. That is what lets the transparency acceptance test demand
byte-identical durable outputs across all seven system variants.

jax is imported lazily (inside functions): the DES and the pure-data
workload registry import chains must stay jax-free.
"""
from __future__ import annotations

import functools

from repro.core import metrics as M
from repro.core.calibrate import (LLM_WEIGHT_SHARDS, ML_ROLES, MOE_SHARDS,
                                  serving_shapes, shard_bytes)
from repro.models import serialize

#: scenario name -> (role, list of payload kinds in IOProfile GET order)
SCENARIO_INPUTS = {
    "LLM-COLD": ("llm", ["weights"] * LLM_WEIGHT_SHARDS + ["prompt"]),
    "LLM-PREFILL": ("llm", ["params", "prompt"]),
    "LLM-DECODE": ("llm", ["params", "kv"]),
    "EMB": ("emb", ["params", "enc_tokens"]),
    "MOE": ("moe", ["weights"] * MOE_SHARDS),
}


# ----------------------------------------------------------- shape structs

def _token_struct(B: int, S: int):
    import jax
    import jax.numpy as jnp
    return jax.ShapeDtypeStruct((B, S), jnp.int32)


@functools.lru_cache(maxsize=None)
def _structs_for(cfg):
    """All shape trees one role needs, from one eval_shape pass set.

    Returns a dict of `ShapeDtypeStruct` trees keyed by struct name.
    Cached per config — configs are frozen dataclasses (hashable).
    """
    import jax
    from repro.models import get_model

    model = get_model(cfg)
    shapes = serving_shapes(cfg)
    (Bp, Sp), (Bd, Sd), (Be, Se) = (shapes["prefill"], shapes["decode"],
                                    shapes["encode"])
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    tok_p, tok_d, tok_e = (_token_struct(Bp, Sp), _token_struct(Bd, Sd),
                           _token_struct(Be, Se))
    logits_p, cache_p = jax.eval_shape(
        model.prefill, params, {"tokens": tok_p})
    _, cache_d = jax.eval_shape(model.prefill, params, {"tokens": tok_d})
    step_tok = _token_struct(Bd, 1)
    logits_step, cache_step = jax.eval_shape(
        model.decode_step, params, cache_d, step_tok)
    cold_tok = _token_struct(Bp, 1)
    logits_cold, _ = jax.eval_shape(
        model.decode_step, params, cache_p, cold_tok)
    logits_e, _ = jax.eval_shape(model.prefill, params, {"tokens": tok_e})
    return {
        "params": params,
        "prompt": tok_p,
        "decode_tokens": tok_d,             # seeds the decode-shaped KV
        "enc_tokens": tok_e,
        "prefill_cache": cache_p,           # LLM-PREFILL durable PUT
        "decode_cache": cache_d,            # LLM-DECODE GET (w/ token)
        "decode_cache_out": cache_step,     # LLM-DECODE durable PUT
        "step_token": step_tok,
        "cold_logits": logits_cold,         # LLM-COLD durable PUT
        "emb_logits": logits_e,             # EMB durable PUT
        "moe_logits": logits_p,             # MOE durable PUT
    }


def role_sizes(cfg, devices: int = 1) -> dict:
    """Exact per-device serialized byte sizes for one calibrated role.

    At tiny scale (``devices=1``, SMOKE config) these are the byte-exact
    sizes of the payloads the handlers read and write; at full scale the
    same shape arithmetic over the published config, divided across the
    serving slice. The serving shapes are implied by the config (see
    `_structs_for`).
    """
    st = _structs_for(cfg)
    n = serialize.tree_nbytes
    return {
        "params_bytes": n(st["params"]) // devices,
        "prompt_bytes": n(st["prompt"]),
        "enc_tokens_bytes": n(st["enc_tokens"]),
        "token_bytes": n(st["step_token"]),
        "kv_prefill_bytes": n(st["prefill_cache"]) // devices,
        "kv_in_bytes": (n(st["decode_cache"]) // devices
                        + n(st["step_token"])),
        "kv_out_bytes": n(st["decode_cache_out"]) // devices,
        "cold_out_bytes": n(st["cold_logits"]),
        "emb_bytes": n(st["emb_logits"]),
        "moe_out_bytes": n(st["moe_logits"]),
    }


# ------------------------------------------------------------ model bundle

def role_config(role: str, cfg=None):
    """The config a role serves: `cfg` when a deployment names one
    (`Workload.model`), else the role's SMOKE config."""
    if cfg is not None:
        return cfg
    from repro.configs import registry
    return registry.get_smoke(ML_ROLES[role])


@functools.lru_cache(maxsize=None)
def bundle(cfg):
    """cfg, model, shape structs and jitted prefill/decode of a config.

    Holds no parameters: at chip size a resident seed copy would sit on
    the device beside every invocation's decoded copy. Jits are cached
    here so the transparency sweep compiles each model once, not once
    per variant.
    """
    import jax
    from repro.models import get_model

    model = get_model(cfg)
    return {
        "cfg": cfg, "model": model,
        "structs": _structs_for(cfg),
        "prefill": jax.jit(model.prefill),
        "decode": jax.jit(model.decode_step),
    }


def _prompt_tokens(b: dict, which: str = "prompt"):
    """Deterministic prompt: a fixed arithmetic progression mod vocab."""
    import jax.numpy as jnp
    import numpy as np
    shape = b["structs"][which].shape
    n = int(np.prod(shape))
    toks = (np.arange(n, dtype=np.int64) * 7 + 3) % b["cfg"].vocab_size
    return jnp.asarray(toks.astype(np.int32).reshape(shape))


def next_token(logits):
    """Greedy next token of each row: (B, S, V) logits -> (B, 1) int32."""
    import jax.numpy as jnp
    return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]


# -------------------------------------------------- seeding (test harness)

def _seed_blobs(b: dict, with_kv: bool) -> tuple[bytes, bytes | None]:
    """(params, decode-ready KV) blobs. Params come from a fixed PRNGKey,
    so every process derives the same random weights. The device arrays
    die with this frame: only invocations' copies stay on the chip."""
    import jax
    params = b["model"].init_params(jax.random.PRNGKey(0))
    kv = None
    if with_kv:
        # a real decode-ready state: prefill a DECODE-shaped fixed
        # prompt (the `decode_cache` struct the handler and
        # calibration declare is derived from exactly this shape —
        # the prompt shape need not coincide), then serialize
        # (cache, next-token) — the decode GET payload
        logits, cache = b["prefill"](
            params, {"tokens": _prompt_tokens(b, "decode_tokens")})
        kv = serialize.dumps((cache, next_token(logits)))
    return serialize.dumps(params), kv


def seed_payloads(scenario: str, cfg=None) -> list[bytes]:
    """The input objects for one scenario, in GET order — what a
    deployment stages in remote storage before invoking. Byte sizes
    match the scenario's `IOProfile` exactly (at SMOKE configs, the
    tiny profiles in `calibration.json`)."""
    role, kinds = SCENARIO_INPUTS[scenario]
    b = bundle(role_config(role, cfg))
    params_blob, kv_blob = _seed_blobs(b, "kv" in kinds)

    out: list[bytes] = []
    shards: list[bytes] = []
    if "weights" in kinds:
        n_shards = kinds.count("weights")
        offs = [0]
        for s in shard_bytes(len(params_blob), n_shards):
            offs.append(offs[-1] + s)
        shards = [params_blob[offs[i]:offs[i + 1]]
                  for i in range(n_shards)]
    for kind in kinds:
        if kind == "weights":
            out.append(shards.pop(0))
        elif kind == "params":
            out.append(params_blob)
        elif kind == "prompt":
            out.append(serialize.dumps(_prompt_tokens(b, "prompt")))
        elif kind == "enc_tokens":
            out.append(serialize.dumps(_prompt_tokens(b, "enc_tokens")))
        elif kind == "kv":
            out.append(kv_blob)
        else:                                    # pragma: no cover
            raise ValueError(kind)
    return out


# ------------------------------------------------------------ handler cores
#
# `cfg` is the deployment's `Workload.model` (None: the role's SMOKE
# config). Each core decodes its own copy of the weights from the bytes
# `ctx.storage` handed it; that copy is the only one on the device.
#
# Spans: ``nexus.handler.decode`` per input (joining shards, then
# `serialize.loads` with its host-to-device copy), ``nexus.handler.step``
# from the jitted call until the outputs the encode reads are ready,
# ``nexus.handler.encode`` (`serialize.dumps`). The step waits only on
# what the encode would wait on anyway.

def _decode(shapes, *bodies):
    with M.span("nexus.handler.decode",
                bytes=sum(len(b) for b in bodies)):
        return serialize.loads(
            shapes, bodies[0] if len(bodies) == 1 else b"".join(bodies))


def _ready(tree):
    import jax
    return jax.block_until_ready(tree)


def _encode(tree) -> bytes:
    with M.span("nexus.handler.encode") as s:
        out = serialize.dumps(tree)
        s.attrs["bytes"] = len(out)
    return out


def llm_cold(shard_bodies, prompt_body, cfg=None) -> bytes:
    """Assemble weights from shards, prefill the prompt, take one decode
    step; the durable output is the step's logits."""
    b = bundle(role_config("llm", cfg))
    params = _decode(b["structs"]["params"], *shard_bodies)
    tokens = _decode(b["structs"]["prompt"], prompt_body)
    with M.span("nexus.handler.step"):
        logits, cache = b["prefill"](params, {"tokens": tokens})
        logits2, _ = _ready(b["decode"](params, cache, next_token(logits)))
    return _encode(logits2)


def llm_prefill(params_body, prompt_body, cfg=None) -> bytes:
    """Prefill: the durable output is the serialized KV cache the decode
    tier would consume."""
    b = bundle(role_config("llm", cfg))
    params = _decode(b["structs"]["params"], params_body)
    tokens = _decode(b["structs"]["prompt"], prompt_body)
    with M.span("nexus.handler.step"):
        _, cache = b["prefill"](params, {"tokens": tokens})
        _ready(cache)
    return _encode(cache)


def llm_decode(params_body, kv_body, cfg=None) -> tuple[bytes, int]:
    """One decode step: deserialize (cache, token), advance the model,
    return (serialized updated cache, next token id)."""
    b = bundle(role_config("llm", cfg))
    params = _decode(b["structs"]["params"], params_body)
    cache, token = _decode(
        (b["structs"]["decode_cache"], b["structs"]["step_token"]), kv_body)
    with M.span("nexus.handler.step"):
        logits, cache2 = _ready(b["decode"](params, cache, token))
    return _encode(cache2), int(next_token(logits)[0, 0])


def emb_encode(params_body, tokens_body, cfg=None) -> bytes:
    """Batch encode: final-position logits as the embedding vectors."""
    b = bundle(role_config("emb", cfg))
    params = _decode(b["structs"]["params"], params_body)
    tokens = _decode(b["structs"]["enc_tokens"], tokens_body)
    with M.span("nexus.handler.step"):
        logits, _ = b["prefill"](params, {"tokens": tokens})
        _ready(logits)
    return _encode(logits)


def moe_infer(shard_bodies, cfg=None) -> bytes:
    """Expert-shard fan-in: reassemble the MoE params from the fetched
    shards, run the fixed prompt through the router + top-k experts."""
    b = bundle(role_config("moe", cfg))
    params = _decode(b["structs"]["params"], *shard_bodies)
    with M.span("nexus.handler.step"):
        logits, _ = b["prefill"](params, {"tokens": _prompt_tokens(b)})
        _ready(logits)
    return _encode(logits)
