"""Shared model layers: norms, RoPE, attention (blocked, GQA, SWA), MLP.

Attention is implemented as a *blocked* (flash-style) pure-jnp
computation so that 32k-token prefill never materializes an S x S score
matrix. On TPU the Pallas kernels in ``repro.kernels`` replace the inner
loop; the jnp path here doubles as their reference and as the CPU
dry-run lowering.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from jax.sharding import AxisType, PartitionSpec

NEG_INF = -1e30


# ------------------------------------------------------------ sharding hints

def shard_hint(x, *dims):
    """Divisibility-guarded with_sharding_constraint against the ambient
    mesh. GSPMD loses activation shardings through nested scan bodies
    (loop-carried values default to replicated — measured as B_global
    tensors inside attention/SSM backward loops, SPerf iteration 2);
    these hints pin batch/feature dims so intermediates stay sharded.

    dims: per-axis logical roles — 'batch' (pod+data), 'model', or None.
    No-op outside a mesh context (unit tests, single-device runs).
    """
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names:
        return x
    # constraints may only name Auto axes (inside shard_map the mapped
    # axes are Manual and already pinned)
    auto = {a for a, t in zip(mesh.axis_names, mesh.axis_types)
            if t == AxisType.Auto}
    fsdp = tuple(a for a in mesh.axis_names
                 if a in ("pod", "data") and a in auto)
    spec = []
    for size, role in zip(x.shape, dims):
        if role == "batch" and fsdp:
            n = 1
            for a in fsdp:
                n *= mesh.shape[a]
            spec.append(fsdp if size % n == 0 else None)
        elif role == "model" and "model" in auto:
            if size % mesh.shape["model"] != 0:
                # do NOT pin: forcing this dim replicated would also
                # forbid GSPMD's flattened-dim sharding (yi-34b's 56
                # heads shard as H*hd) — leave the tensor free instead
                return x
            spec.append("model")
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(x, PartitionSpec(*spec))



# ---------------------------------------------------------------- init utils

def dense_init(rng, shape, in_axis=0, dtype=jnp.bfloat16):
    """LeCun-normal over the contracting dimension."""
    fan_in = shape[in_axis]
    return (jax.random.normal(rng, shape, dtype=jnp.float32)
            * (1.0 / math.sqrt(max(fan_in, 1)))).astype(dtype)


def split_rngs(rng, n):
    return list(jax.random.split(rng, n))


# --------------------------------------------------------------------- norms

def rms_norm(x, scale, eps=1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------- RoPE

def rope_freqs(head_dim, theta):
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponents)                        # (hd/2,)


def apply_rope(x, positions, theta):
    """x: (..., S, n_heads, head_dim); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                            # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., None, :]                      # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoid_pos_embed(positions, d_model):
    """Absolute sinusoidal embeddings (enc-dec archs). positions: (...,)."""
    half = d_model // 2
    freqs = jnp.exp(-math.log(10_000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------- attention (core)

def _gqa_scores(q, k):
    """q: (B, Sq, K, G, hd), k: (B, Sk, K, hd) -> (B, K, G, Sq, Sk) fp32."""
    return jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                      preferred_element_type=jnp.float32)


def _gqa_values(p, v):
    """p: (B, K, G, Sq, Sk); v: (B, Sk, K, hd) -> (B, Sq, K, G, hd)."""
    return jnp.einsum("bkgqs,bskd->bqkgd", p, v,
                      preferred_element_type=jnp.float32)


def blocked_causal_attention(q, k, v, *, window=0, q_block=512, kv_block=512,
                             q_offset=0, causal=True, inner_remat=False):
    """Flash-style blocked attention in pure jnp.

    q: (B, Sq, H, hd); k, v: (B, Sk, K, hd) with H = K * G.
    ``window`` > 0 enables sliding-window masking AND bounds the kv range
    actually visited per q block (so SWA prefill is O(S*W), not O(S^2)).
    ``q_offset``: absolute position of q[:, 0] (k positions start at 0).
    ``causal=False`` gives bidirectional attention (encoders, cross-attn).
    Returns (B, Sq, H, hd) in q.dtype.
    """
    B, Sq, H, hd = q.shape
    Sk, K = k.shape[1], k.shape[2]
    G = H // K
    scale = 1.0 / math.sqrt(hd)

    q_block = min(q_block, Sq)
    kv_block = min(kv_block, Sk)
    # pad to multiples
    pad_q = (-Sq) % q_block
    pad_k = (-Sk) % kv_block
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    nq, nk = q.shape[1] // q_block, k.shape[1] // kv_block

    qb = q.reshape(B, nq, q_block, K, G, hd)
    kb = k.reshape(B, nk, kv_block, K, hd)
    vb = v.reshape(B, nk, kv_block, K, hd)

    # number of kv blocks a q block ever needs (static)
    if window > 0:
        span = window + q_block
        nk_needed = min(nk, -(-span // kv_block) + 1)
    else:
        nk_needed = nk

    k_pos_base = jnp.arange(kv_block)

    def q_step(_, qi):
        q_i = qb[:, qi] * scale                              # (B,qc,K,G,hd)
        q_pos = q_offset + qi * q_block + jnp.arange(q_block)

        if window > 0:
            # earliest kv block that can be visible to this q block
            lo = jnp.maximum(qi * q_block + q_block - 1 - (window - 1 + kv_block - 1), 0)
            first = jnp.clip(lo // kv_block, 0, max(nk - nk_needed, 0))
        else:
            first = 0

        def kv_step(carry, j):
            m, l, acc = carry
            kj = jax.lax.dynamic_index_in_dim(kb, first + j, axis=1, keepdims=False)
            vj = jax.lax.dynamic_index_in_dim(vb, first + j, axis=1, keepdims=False)
            k_pos = (first + j) * kv_block + k_pos_base
            s = _gqa_scores(q_i, kj)                          # (B,K,G,qc,kc) f32
            mask = k_pos[None, :] < Sk                        # kv padding
            if causal:
                mask &= k_pos[None, :] <= q_pos[:, None]
            if window > 0:
                mask &= (q_pos[:, None] - k_pos[None, :]) < window
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p, vj.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, K, G, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, K, G, q_block), jnp.float32)
        a0 = jnp.zeros((B, K, G, q_block, hd), jnp.float32)
        kv_body = jax.checkpoint(kv_step) if inner_remat else kv_step
        (m, l, acc), _ = jax.lax.scan(kv_body, (m0, l0, a0),
                                      jnp.arange(nk_needed))
        out = acc / jnp.maximum(l, 1e-30)[..., None]          # (B,K,G,qc,hd)
        out = out.transpose(0, 3, 1, 2, 4)                    # (B,qc,K,G,hd)
        return None, out.astype(q.dtype)

    body = jax.checkpoint(q_step) if inner_remat else q_step
    _, outs = jax.lax.scan(body, None, jnp.arange(nq))        # (nq,B,qc,K,G,hd)
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, nq * q_block, H, hd)
    return out[:, :Sq]


def full_attention(q, k, v, mask):
    """Unblocked attention for short sequences / smoke tests.

    q: (B, Sq, H, hd); k, v: (B, Sk, K, hd); mask broadcastable to
    (B, 1, 1, Sq, Sk). Returns (B, Sq, H, hd).
    """
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    qg = q.reshape(B, Sq, K, H // K, hd) / math.sqrt(hd)
    s = _gqa_scores(qg, k)
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = _gqa_values(p, v)
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, slot_pos, pos, *, window=0):
    """Single-token attention against a (ring) KV cache.

    q: (B, 1, H, hd); k_cache, v_cache: (B, W, K, hd);
    slot_pos: (B, W) absolute position stored in each slot (-1 = empty);
    pos: (B,) current absolute position of the query token.
    """
    B, _, H, hd = q.shape
    K = k_cache.shape[2]
    qg = q.reshape(B, 1, K, H // K, hd) / math.sqrt(hd)
    s = _gqa_scores(qg, k_cache)                              # (B,K,G,1,W)
    valid = (slot_pos >= 0) & (slot_pos <= pos[:, None])
    if window > 0:
        valid &= (pos[:, None] - slot_pos) < window
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = _gqa_values(p, v_cache)
    return out.reshape(B, 1, H, hd).astype(q.dtype)


# ------------------------------------------------------------ attention layer

def init_attention(rng, cfg, dtype):
    D, H, K, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    r = split_rngs(rng, 4)
    p = {
        "wq": dense_init(r[0], (D, H * hd), 0, dtype),
        "wk": dense_init(r[1], (D, K * hd), 0, dtype),
        "wv": dense_init(r[2], (D, K * hd), 0, dtype),
        "wo": dense_init(r[3], (H * hd, D), 0, dtype),
    }
    if cfg.attn_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((K * hd,), dtype)
        p["bv"] = jnp.zeros((K * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def _project_qkv(p, cfg, x):
    B, S, _ = x.shape
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.attn_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = shard_hint(q.reshape(B, S, H, hd), "batch", None, "model", None)
    k = shard_hint(k.reshape(B, S, K, hd), "batch", None, "model", None)
    v = shard_hint(v.reshape(B, S, K, hd), "batch", None, "model", None)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def attention_layer(p, cfg, x, *, positions=None, use_rope=True,
                    causal=True, blocked_threshold=2048):
    """Self-attention over a full sequence (train / prefill / encoder).

    Returns (out, (k, v)) so callers can build a KV cache.
    """
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, cfg, x)
    if positions is None:
        positions = jnp.arange(S)[None, :]
    if use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    if S > blocked_threshold or cfg.sliding_window:
        out = blocked_causal_attention(q, k, v, window=cfg.sliding_window,
                                       causal=causal,
                                       inner_remat=cfg.inner_remat)
    else:
        if causal:
            # mask[b, q, k] = k_pos <= q_pos
            mask = positions[:, None, :] <= positions[:, :, None]
            mask = mask[:, None, None, :, :]
        else:
            mask = jnp.ones((1, 1, 1, S, S), bool)
        out = full_attention(q, k, v, mask)
    out = shard_hint(out, "batch", None, "model", None)
    out = out.reshape(B, S, cfg.num_heads * cfg.head_dim)
    return out @ p["wo"], (k, v)


def attention_decode_layer(p, cfg, x, k_cache, v_cache, slot_pos, pos, *,
                           use_rope=True):
    """One-token self-attention against a ring cache.

    x: (B, 1, D); pos: (B,) absolute position of this token. ``slot_pos``
    must ALREADY include the current token (the stack updates it once,
    outside the layer scan, since every layer writes the same slot).
    Returns (out, (k_cache, v_cache)) with this layer's K/V written in.

    ``cfg.uniform_decode``: serving batches that decode in lockstep share
    one ring slot, so the cache write lowers to a width-1
    dynamic-update-slice on the (sharded) W axis instead of a per-batch
    scatter — GSPMD rewrites the scatter as a full-cache masked select,
    which dominated serve_step HBM traffic (SPerf iteration: llama3).
    """
    B = x.shape[0]
    q, k, v = _project_qkv(p, cfg, x)
    if use_rope:
        q = apply_rope(q, pos[:, None], cfg.rope_theta)
        k = apply_rope(k, pos[:, None], cfg.rope_theta)
    # decode shards head_dim over `model` (cache rule): pin q/k/v the
    # same way so the ring write stays partition-local (no resharding)
    q = shard_hint(q, "batch", None, None, "model")
    k = shard_hint(k, "batch", None, None, "model")
    v = shard_hint(v, "batch", None, None, "model")
    W = k_cache.shape[1]
    if cfg.uniform_decode:
        slot0 = (pos[0] % W).astype(jnp.int32)
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            k_cache, k[:, :1], slot0, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            v_cache, v[:, :1], slot0, axis=1)
    else:
        slot = (pos % W).astype(jnp.int32)
        b_idx = jnp.arange(B)
        k_cache = k_cache.at[b_idx, slot].set(k[:, 0])
        v_cache = v_cache.at[b_idx, slot].set(v[:, 0])
    out = decode_attention(q, k_cache, v_cache, slot_pos, pos,
                           window=cfg.sliding_window)
    out = out.reshape(B, 1, cfg.num_heads * cfg.head_dim)
    return out @ p["wo"], (k_cache, v_cache)


def cross_attention_layer(p, cfg, x, k_cache, v_cache):
    """Cross-attention against precomputed encoder K/V (no masking)."""
    B, S, _ = x.shape
    H, hd = cfg.num_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    Sk = k_cache.shape[1]
    mask = jnp.ones((1, 1, 1, S, Sk), bool)
    out = full_attention(q, k_cache, v_cache, mask)
    out = out.reshape(B, S, H * hd)
    return out @ p["wo"]


# ----------------------------------------------------------------------- MLP

def init_mlp(rng, d_model, d_ff, dtype):
    r = split_rngs(rng, 3)
    return {
        "w_gate": dense_init(r[0], (d_model, d_ff), 0, dtype),
        "w_up": dense_init(r[1], (d_model, d_ff), 0, dtype),
        "w_down": dense_init(r[2], (d_ff, d_model), 0, dtype),
    }


def mlp_layer(p, x):
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]
