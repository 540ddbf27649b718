"""Plain forward pass of a qwen3_moe decoder's share of experts, in float32.

The comparison that decides a run's ``correct`` holds the served outputs
against this. It imports nothing of the system under test and reads the
architecture from the configuration file alone: token embedding, then per
layer RMSNorm, the query and key projections each RMS-normed per head
and then rotated (rotate-half form), causal grouped-query attention,
RMSNorm and the expert layer, each with a residual; a final RMSNorm and
the head.

The expert layer is written densely: the router scores every token
against all ``published.num_experts`` experts, softmax, keeps the top
``num_experts_per_tok`` and renormalises their probabilities to sum to
one; then every token goes through each of the experts held here
(``num_experts`` of them from ``first_expert``), a SwiGLU of width
``moe_intermediate_size``, weighted by its gate, which is zero where the
expert is not among the token's top k. What experts held elsewhere would
add is left out, as the served layer leaves it out.

As in `reference.llama`, whose helpers this module loads by path: every
matrix product runs at ``Precision.HIGHEST``, layer by layer over blocks
of rows, and ``precision="float8_e4m3fn"`` is the control, every operand
of every product (router and expert products included) rounded to
float8 e4m3 under one scale per tensor.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import architectures

_llama = architectures.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "llama.py"),
    "chipbench_reference_llama_helpers")
HIGHEST = _llama.HIGHEST
PRECISIONS = _llama.PRECISIONS


@functools.lru_cache(maxsize=None)
def _layer_fn(H: int, K: int, hd: int, theta: float, eps: float, k: int,
              first: int, n: int, precision: str):
    r = _llama._rounding(precision)
    rms, rope = _llama._rms, _llama._rope

    def mm(a, w):
        return jnp.matmul(r(a), r(w.astype(jnp.float32)), precision=HIGHEST)

    def experts(h, lw):
        probs = jax.nn.softmax(mm(h, lw["moe.router"]), axis=-1)
        top, idx = jax.lax.top_k(probs, k)
        top = top / top.sum(-1, keepdims=True)
        gate = jnp.sum(jax.nn.one_hot(idx - first, n) * top[..., None],
                       axis=-2)                             # (B, S, n)
        wg, wu, wd = (lw[f"moe.{m}"].astype(jnp.float32)
                      for m in ("w_gate", "w_up", "w_down"))
        g = jnp.einsum("bsd,edf->bsef", r(h), r(wg), precision=HIGHEST)
        u = jnp.einsum("bsd,edf->bsef", r(h), r(wu), precision=HIGHEST)
        a = jax.nn.silu(g) * u * gate[..., None]
        return jnp.einsum("bsef,efd->bsd", r(a), r(wd), precision=HIGHEST)

    def layer(x, lw):
        B, S, _ = x.shape
        h = rms(x, lw["ln1"], eps)
        q = rope(rms(mm(h, lw["attn.wq"]).reshape(B, S, H, hd),
                     lw["attn.q_norm"], eps), theta)
        kk = rope(rms(mm(h, lw["attn.wk"]).reshape(B, S, K, hd),
                      lw["attn.k_norm"], eps), theta)
        v = mm(h, lw["attn.wv"]).reshape(B, S, K, hd)
        qg = q.reshape(B, S, K, H // K, hd)
        s = jnp.einsum("bqkgd,bskd->bkgqs", r(qg), r(kk),
                       precision=HIGHEST) / math.sqrt(hd)
        causal = jnp.tril(jnp.ones((S, S), bool))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("bkgqs,bskd->bqkgd", r(p), r(v), precision=HIGHEST)
        x = x + mm(o.reshape(B, S, H * hd), lw["attn.wo"])
        x = x + experts(rms(x, lw["ln2"], eps), lw)
        return x, kk, v
    return jax.jit(layer)


def forward(spec: dict, w: dict, tokens, *, want=("logits",),
            precision: str = "float32", row_block: int = 8) -> dict:
    """The reference's outputs for `tokens` (B, S) under weights `w`
    (leaf name -> array, `arch.qwen3_moe.shapes` layout), as numpy
    float32: ``logits`` (B, 1, V) at the last position, ``kv`` every
    layer's keys (after the norm and the rotation) and values,
    (L, B, S, K, hd) each.
    """
    L = spec["num_hidden_layers"]
    H, K = spec["num_attention_heads"], spec["num_key_value_heads"]
    hd, eps = spec["head_dim"], spec["rms_norm_eps"]
    layer = _layer_fn(H, K, hd, float(spec["rope_theta"]), eps,
                      spec["num_experts_per_tok"], spec["first_expert"],
                      spec["num_experts"], precision)
    tokens = np.asarray(tokens)
    blocks = [jnp.asarray(tokens[i:i + row_block])
              for i in range(0, tokens.shape[0], row_block)]
    xs = [_llama._embed_fn(precision)(w["embed"], t) for t in blocks]
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
        tied = spec["tie_word_embeddings"]
        head = _llama._head_fn(eps, tied, precision)
        hw = w["embed"] if tied else w["lm_head"]
        out["logits"] = np.concatenate(
            [np.asarray(head(x, w["final_norm"], hw)) for x in xs])
    return out
