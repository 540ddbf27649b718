"""Plain forward pass of a llama-architecture decoder, in float32.

The comparison that decides a run's ``correct`` holds the served outputs
against this. It imports nothing of the system under test and reads the
architecture from the configuration file alone: token embedding, then per
layer RMSNorm, rotary embedding (rotate-half form) on the queries and
keys, causal grouped-query attention, RMSNorm and a SwiGLU MLP, each
with a residual; a final RMSNorm and a head that is the embedding's
transpose where ``tie_word_embeddings`` is set.

Every matrix product runs at ``Precision.HIGHEST``, so a TPU computes it
in float32 and not in one bfloat16 pass. The work goes layer by layer
over blocks of rows, so that only one layer's float32 weights and one
block's activations are live at a time.

``precision="float8_e4m3fn"`` is the control: the same computation with
every operand of every product (weights, activations, attention scores
and probabilities) rounded to float8 e4m3 under one scale per tensor,
and float32 accumulation. A program that computed in float8 instead of
the bfloat16 the configuration states would read like it.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
PRECISIONS = ("float32", "float8_e4m3fn")
#: largest finite float8 e4m3fn value
E4M3_MAX = 448.0


def _fp8(x):
    s = jnp.max(jnp.abs(x)) / E4M3_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rounding(precision: str):
    if precision == "float32":
        return lambda x: x
    if precision == "float8_e4m3fn":
        return _fp8
    raise ValueError(f"unknown precision {precision!r}")


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, theta):
    """x: (B, S, heads, hd); positions 0..S-1."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs      # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.lru_cache(maxsize=None)
def _layer_fn(H: int, K: int, hd: int, theta: float, eps: float,
              precision: str):
    r = _rounding(precision)

    def mm(a, w):
        return jnp.matmul(r(a), r(w.astype(jnp.float32)), precision=HIGHEST)

    def layer(x, lw):
        B, S, _ = x.shape
        h = _rms(x, lw["ln1"], eps)
        q = _rope(mm(h, lw["attn.wq"]).reshape(B, S, H, hd), theta)
        k = _rope(mm(h, lw["attn.wk"]).reshape(B, S, K, hd), theta)
        v = mm(h, lw["attn.wv"]).reshape(B, S, K, hd)
        qg = q.reshape(B, S, K, H // K, hd)
        s = jnp.einsum("bqkgd,bskd->bkgqs", r(qg), r(k),
                       precision=HIGHEST) / math.sqrt(hd)
        causal = jnp.tril(jnp.ones((S, S), bool))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("bkgqs,bskd->bqkgd", r(p), r(v), precision=HIGHEST)
        x = x + mm(o.reshape(B, S, H * hd), lw["attn.wo"])
        h2 = _rms(x, lw["ln2"], eps)
        m = jax.nn.silu(mm(h2, lw["mlp.w_gate"])) * mm(h2, lw["mlp.w_up"])
        x = x + mm(m, lw["mlp.w_down"])
        return x, k, v
    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _embed_fn(precision: str):
    r = _rounding(precision)
    return jax.jit(lambda table, tokens: r(table[tokens].astype(jnp.float32)))


@functools.lru_cache(maxsize=None)
def _head_fn(eps: float, tied: bool, precision: str):
    r = _rounding(precision)

    def head(x, norm, w):
        h = _rms(x[:, -1:], norm, eps)
        w = w.astype(jnp.float32)
        return jnp.matmul(r(h), r(w.T if tied else w), precision=HIGHEST)
    return jax.jit(head)


def forward(spec: dict, w: dict, tokens, *, want=("logits",),
            precision: str = "float32", row_block: int = 8) -> dict:
    """The reference's outputs for `tokens` (B, S) under weights `w`
    (leaf name -> array, `weights.shapes` layout), as numpy float32.

    `want` names what to return: ``logits`` (B, 1, V) at the last
    position, ``kv`` every layer's keys (after the rotary embedding) and
    values, (L, B, S, K, hd) each.
    """
    L = spec["num_hidden_layers"]
    H, K = spec["num_attention_heads"], spec["num_key_value_heads"]
    hd, eps = spec["head_dim"], spec["rms_norm_eps"]
    tied = spec["tie_word_embeddings"]
    layer = _layer_fn(H, K, hd, float(spec["rope_theta"]), eps, precision)
    tokens = np.asarray(tokens)
    blocks = [jnp.asarray(tokens[i:i + row_block])
              for i in range(0, tokens.shape[0], row_block)]
    xs = [_embed_fn(precision)(w["embed"], t) for t in blocks]
    ks, vs = [], []
    for i in range(L):
        lw = {n[len("layers."):]: a[i] for n, a in w.items()
              if n.startswith("layers.")}
        kb, vb = [], []
        for j, x in enumerate(xs):
            xs[j], k, v = layer(x, lw)
            if "kv" in want:
                kb.append(np.asarray(k))
                vb.append(np.asarray(v))
        del lw
        if "kv" in want:
            ks.append(np.concatenate(kb))
            vs.append(np.concatenate(vb))
    out = {}
    if "kv" in want:
        out["k"], out["v"] = np.stack(ks), np.stack(vs)
    if "logits" in want:
        head = _head_fn(eps, tied, precision)
        hw = w["embed"] if tied else w["lm_head"]
        out["logits"] = np.concatenate(
            [np.asarray(head(x, w["final_norm"], hw)) for x in xs])
    return out
