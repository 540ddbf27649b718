"""Decoder-only LM assembly for dense / vlm / moe / ssm / hybrid families.

Layers are parameter-stacked (leading L axis) and applied with
``jax.lax.scan`` so the lowered HLO contains ONE layer body regardless of
depth — essential for compiling 80-layer configs against 512-device
meshes in reasonable time. Remat policy wraps the scan body.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.models import kv_cache as kvc
from repro.models import layers as L
from repro.models import mamba as M
from repro.models import moe as MOE

LOSS_CHUNK = 2048
#: the moe family's per-layer counter: how many assignments each held
#: expert computed. It leaves the layer scan beside the cache entries and
#: `prefill` takes it out of the cache.
EXPERT_TOKENS = "expert_tokens"


# ------------------------------------------------------------------- params

def init_layer_params(rng, cfg, dtype):
    fam = cfg.family
    r = L.split_rngs(rng, 8)
    p = {}
    if fam in ("dense", "vlm", "moe", "hybrid"):
        p["ln1"] = jnp.ones((cfg.d_model,), dtype)
        p["attn"] = L.init_attention(r[0], cfg, dtype)
    if fam in ("dense", "vlm", "hybrid"):
        p["ln2"] = jnp.ones((cfg.d_model,), dtype)
        p["mlp"] = L.init_mlp(r[1], cfg.d_model, cfg.d_ff, dtype)
    if fam == "moe":
        p["ln2"] = jnp.ones((cfg.d_model,), dtype)
        p["moe"] = MOE.init_moe(r[2], cfg, dtype)
    if fam == "ssm":
        p["ln1"] = jnp.ones((cfg.d_model,), dtype)
        p["mamba"] = M.init_mamba(r[3], cfg, dtype)
    if fam == "hybrid":
        p["mamba"] = M.init_mamba(r[4], cfg, dtype)
        p["bn_attn"] = jnp.ones((cfg.d_model,), dtype)
        p["bn_mamba"] = jnp.ones((cfg.d_model,), dtype)
    return p


def init_params(rng, cfg):
    dtype = jnp.dtype(cfg.param_dtype)
    r_embed, r_layers, r_head = jax.random.split(rng, 3)
    layer_rngs = jax.random.split(r_layers, cfg.num_layers)
    per_layer = [init_layer_params(lr, cfg, dtype) for lr in layer_rngs]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer)
    params = {
        "embed": L.dense_init(r_embed, (cfg.vocab_size, cfg.d_model), 1, dtype),
        "layers": stacked,
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(
            r_head, (cfg.d_model, cfg.vocab_size), 0, dtype)
    return params


# ----------------------------------------------------------------- sublayers

def _seq_sublayers(cfg, lp, x, mode, ssm_state=None, cache_len=0):
    """One layer over a full sequence. Returns (x, cache_out, aux)."""
    fam = cfg.family
    aux = jnp.zeros((), jnp.float32)
    cache_out = {}
    x = L.shard_hint(x, "batch", None, None)
    h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)

    if fam in ("dense", "vlm", "moe"):
        attn_out, (k, v) = L.attention_layer(lp["attn"], cfg, h)
        x = x + attn_out
        if mode == "prefill":
            cache_out["k"], cache_out["v"] = _ring_kv(cfg, k, v, cache_len)
    elif fam == "ssm":
        m_out, st = M.mamba_layer(lp["mamba"], cfg, h, ssm_state)
        x = x + m_out
        if mode == "prefill":
            cache_out.update(st)
        return x, cache_out, aux                       # mamba block has no MLP
    elif fam == "hybrid":
        attn_out, (k, v) = L.attention_layer(lp["attn"], cfg, h)
        m_out, st = M.mamba_layer(lp["mamba"], cfg, h, ssm_state)
        fused = 0.5 * (L.rms_norm(attn_out, lp["bn_attn"], cfg.norm_eps)
                       + L.rms_norm(m_out, lp["bn_mamba"], cfg.norm_eps))
        x = x + fused
        if mode == "prefill":
            cache_out["k"], cache_out["v"] = _ring_kv(cfg, k, v, cache_len)
            cache_out.update(st)

    h2 = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
    if fam == "moe":
        with jax.named_scope("moe"):
            moe_out, aux, tokens = MOE.moe_layer(lp["moe"], cfg, h2)
        x = x + moe_out
        if mode == "prefill":
            cache_out[EXPERT_TOKENS] = tokens
    else:
        x = x + L.mlp_layer(lp["mlp"], h2)
    return x, cache_out, aux


def _ring_kv(cfg, k, v, cache_len=0):
    S = k.shape[1]
    W = kvc.cache_width(cfg, max(S, cache_len))
    if W == S:
        return k, v
    zk = jnp.zeros((k.shape[0], W) + k.shape[2:], k.dtype)
    return (kvc.write_prefill_entries(zk, k, None),
            kvc.write_prefill_entries(zk, v, None))


def _decode_sublayers(cfg, lp, x, layer_cache, slot_pos, pos):
    """One layer, one token. Returns (x, new_layer_cache)."""
    fam = cfg.family
    new_cache = {}
    h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)

    if fam in ("dense", "vlm", "moe"):
        attn_out, (k_c, v_c) = L.attention_decode_layer(
            lp["attn"], cfg, h, layer_cache["k"], layer_cache["v"],
            slot_pos, pos)
        x = x + attn_out
        new_cache["k"], new_cache["v"] = k_c, v_c
    elif fam == "ssm":
        m_out, st = M.mamba_decode_step(
            lp["mamba"], cfg, h, {"conv": layer_cache["conv"],
                                  "ssm": layer_cache["ssm"]})
        x = x + m_out
        new_cache.update(st)
        return x, new_cache
    elif fam == "hybrid":
        attn_out, (k_c, v_c) = L.attention_decode_layer(
            lp["attn"], cfg, h, layer_cache["k"], layer_cache["v"],
            slot_pos, pos)
        m_out, st = M.mamba_decode_step(
            lp["mamba"], cfg, h, {"conv": layer_cache["conv"],
                                  "ssm": layer_cache["ssm"]})
        fused = 0.5 * (L.rms_norm(attn_out, lp["bn_attn"], cfg.norm_eps)
                       + L.rms_norm(m_out, lp["bn_mamba"], cfg.norm_eps))
        x = x + fused
        new_cache["k"], new_cache["v"] = k_c, v_c
        new_cache.update(st)

    h2 = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
    if fam == "moe":
        # decode: T = B tokens only — dense (dropless) dispatch is both
        # exact and cheaper than sort/scatter at this scale.
        moe_out, _ = MOE.moe_dense(lp["moe"], cfg, h2)
        x = x + moe_out
    else:
        x = x + L.mlp_layer(lp["mlp"], h2)
    return x, new_cache


# ------------------------------------------------------------------- stacks

def _remat(fn, cfg):
    if cfg.remat_policy == "none":
        return fn
    if cfg.remat_policy == "full":
        return jax.checkpoint(fn)
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)


def run_stack(cfg, params, x, mode, cache_len=0):
    """Run the layer stack over a full sequence.

    mode: 'train' | 'prefill'. Returns (hidden, stacked_cache, aux_loss).
    """
    def body(carry, lp):
        h, aux = carry
        h, cache_out, aux_l = _seq_sublayers(cfg, lp, h, mode,
                                             cache_len=cache_len)
        return (h, aux + aux_l), cache_out

    (x, aux), caches = jax.lax.scan(
        _remat(body, cfg), (x, jnp.zeros((), jnp.float32)), params["layers"])
    return L.rms_norm(x, params["final_norm"], cfg.norm_eps), caches, aux


def run_stack_decode(cfg, params, x, cache, pos):
    """Run the stack for one decode token; cache leaves have leading L."""
    layer_caches = {k: v for k, v in cache.items()
                    if k not in ("pos", "slot_pos", "cross_k", "cross_v")}
    slot_pos = cache.get("slot_pos")
    if slot_pos is not None:
        W = slot_pos.shape[1]
        b_idx = jnp.arange(slot_pos.shape[0])
        slot = (pos % W).astype(jnp.int32)
        slot_pos = slot_pos.at[b_idx, slot].set(pos.astype(jnp.int32))

    def body(h, xs):
        lp, lc = xs
        h, new_lc = _decode_sublayers(cfg, lp, h, lc, slot_pos, pos)
        return h, new_lc

    x, new_caches = jax.lax.scan(body, x, (params["layers"], layer_caches))
    new_cache = dict(cache)
    new_cache.update(new_caches)
    if slot_pos is not None:
        new_cache["slot_pos"] = slot_pos
    new_cache["pos"] = pos + 1
    return L.rms_norm(x, params["final_norm"], cfg.norm_eps), new_cache


# ----------------------------------------------------------------- lm heads

def _lm_head(cfg, params):
    return params["embed"].T if cfg.tie_embeddings else params["lm_head"]


def chunked_ce_loss(cfg, params, hidden, targets, chunk=LOSS_CHUNK):
    """Cross-entropy without materializing full (B, S, V) logits."""
    B, S, D = hidden.shape
    head = _lm_head(cfg, params)
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)), constant_values=-1)
    n = hidden.shape[1] // chunk
    hc = hidden.reshape(B, n, chunk, D).transpose(1, 0, 2, 3)
    tc = targets.reshape(B, n, chunk).transpose(1, 0, 2)

    def step(acc, xs):
        h, t = xs
        logits = (h @ head).astype(jnp.float32)         # (B, chunk, V)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(t, 0)[..., None], axis=-1)[..., 0]
        valid = (t >= 0).astype(jnp.float32)
        nll = (lse - gold) * valid
        return (acc[0] + nll.sum(), acc[1] + valid.sum()), None

    (tot, cnt), _ = jax.lax.scan(step, (jnp.zeros(()), jnp.zeros(())), (hc, tc))
    return tot / jnp.maximum(cnt, 1.0)


# ------------------------------------------------------------------ top-level

def embed_tokens(cfg, params, tokens):
    return params["embed"][tokens]


def forward_train(cfg, params, batch):
    """Returns (loss, metrics). batch: tokens/inputs_embeds + targets."""
    if cfg.embed_input:
        x = batch["inputs_embeds"].astype(jnp.dtype(cfg.dtype))
    else:
        x = embed_tokens(cfg, params, batch["tokens"])
    hidden, _, aux = run_stack(cfg, params, x, "train")
    loss = chunked_ce_loss(cfg, params, hidden, batch["targets"])
    total = loss + aux
    return total, {"ce_loss": loss, "aux_loss": aux}


def prefill(cfg, params, batch, cache_len=None, counters=False):
    """Process the prompt; returns (last-token logits, decode cache), and
    with `counters` the step's counters as well: for the moe family
    ``{"expert_tokens": (L, held) int32}``, else ``{}``."""
    if cfg.embed_input:
        x = batch["inputs_embeds"].astype(jnp.dtype(cfg.dtype))
        B, S = x.shape[:2]
    else:
        x = embed_tokens(cfg, params, batch["tokens"])
        B, S = batch["tokens"].shape
    hidden, caches, _ = run_stack(cfg, params, x, "prefill",
                                  cache_len=cache_len or S)
    step = {}
    if EXPERT_TOKENS in caches:
        step[EXPERT_TOKENS] = caches.pop(EXPERT_TOKENS)
    logits = (hidden[:, -1:] @ _lm_head(cfg, params)).astype(jnp.float32)

    cache = {"pos": jnp.full((B,), S, jnp.int32)}
    cache.update(caches)
    if cfg.family in ("dense", "vlm", "moe", "hybrid"):
        W = kvc.cache_width(cfg, max(cache_len or S, S))
        cache["slot_pos"] = kvc.prefill_slot_pos(S, W, B)
    return (logits, cache, step) if counters else (logits, cache)


def decode_step(cfg, params, cache, token):
    """One token: (B, 1) int32 -> (logits (B, 1, V), new cache)."""
    x = embed_tokens(cfg, params, token)
    hidden, new_cache = run_stack_decode(cfg, params, x, cache, cache["pos"])
    logits = (hidden @ _lm_head(cfg, params)).astype(jnp.float32)
    return logits, new_cache
