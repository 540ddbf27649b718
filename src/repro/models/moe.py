"""Mixture-of-Experts FFN with top-k routing over a held share of the experts.

An expert layer holds ``cfg.held_experts`` consecutive experts from
``cfg.first_expert`` (all ``cfg.num_experts`` by default), as one chip of
an expert-parallel deployment holds its share. The router always keeps
its full width: every token is routed over all ``num_experts`` experts,
top-k, with the k gates renormalised. The layer then computes only its
held experts' part of the result, for every token routed to them, and
leaves out what the experts held elsewhere would add.

Two implementations of the same mathematics:

* `moe_layer` (served, prefill and training): the assignments are sorted
  by held expert and each expert's SwiGLU runs over its own group with a
  grouped matmul (``jax.lax.ragged_dot``). Dropless: no capacity buffer,
  every assignment to a held expert is computed, however uneven the
  routing. It also returns each held expert's group size.
* `moe_dense`: every token through every held expert, masked by its
  gate. Exact but ``num_experts/k`` times the expert FLOPs: the
  correctness oracle in tests, and the decode path, where a step has few
  tokens.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init, shard_hint, split_rngs


def init_moe(rng, cfg, dtype):
    D, F, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    n = cfg.expert_share[1]
    r = split_rngs(rng, 4)
    return {
        "router": dense_init(r[0], (D, E), 0, jnp.dtype(cfg.router_dtype)),
        "w_gate": dense_init(r[1], (n, D, F), 1, dtype),
        "w_up": dense_init(r[2], (n, D, F), 1, dtype),
        "w_down": dense_init(r[3], (n, F, D), 1, dtype),
    }


def _route(p, cfg, xf):
    """Router in fp32 over all experts. xf: (T, D) -> gates (T, k),
    idx (T, k), aux_loss."""
    logits = xf.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                 # (T, E)
    gates, idx = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balancing auxiliary loss
    E = cfg.num_experts
    me = probs.mean(axis=0)                             # mean router prob
    ce = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    ce = ce / jnp.maximum(ce.sum(), 1.0)
    aux = E * jnp.sum(me * ce) * cfg.router_aux_loss
    return gates, idx, aux


def moe_layer(p, cfg, x):
    """Dropless grouped dispatch over the held experts.

    x: (B, S, D) -> (out, aux_loss, tokens): ``tokens`` (held,) int32 is
    how many assignments each held expert computed.
    """
    B, S, D = x.shape
    k = cfg.num_experts_per_tok
    first, n = cfg.expert_share
    T = B * S
    A = T * k                                           # assignments
    xf = shard_hint(x.reshape(T, D), "batch", None)
    gates, idx, aux = _route(p, cfg, xf)

    local = idx - first                                 # (T, k)
    mine = (local >= 0) & (local < n)
    # assignments to experts held elsewhere sort last, as group n, which
    # no matmul reads
    group = jnp.where(mine, local, n).reshape(A)
    order = jnp.argsort(group)                          # stable
    tokens = jnp.sum(group[:, None] == jnp.arange(n), axis=0,
                     dtype=jnp.int32)                   # (n,) group sizes
    xs = xf[order // k]                                 # (A, D) by group
    h = jax.nn.silu(jax.lax.ragged_dot(xs, p["w_gate"], tokens))
    h = h * jax.lax.ragged_dot(xs, p["w_up"], tokens)
    ys = jax.lax.ragged_dot(h, p["w_down"], tokens)     # (A, D)
    y = ys[jnp.argsort(order)].reshape(T, k, D).astype(jnp.float32)
    # rows past the held groups are not products: select, never scale
    y = jnp.where(mine[..., None], y * gates[..., None], 0.0)
    return y.sum(axis=1).astype(x.dtype).reshape(B, S, D), aux, tokens


def moe_dense(p, cfg, x):
    """Masked-dense dispatch over the held experts (oracle; E/k x
    FLOPs). x: (B, S, D) -> (out, aux_loss)."""
    B, S, D = x.shape
    E = cfg.num_experts
    first, n = cfg.expert_share
    T = B * S
    xf = x.reshape(T, D)
    gates, idx, aux = _route(p, cfg, xf)
    # combine weight per (token, expert), then the held experts' columns
    w = jnp.zeros((T, E), jnp.float32)
    w = w.at[jnp.arange(T)[:, None], idx].add(gates)[:, first:first + n]
    h = jax.nn.silu(jnp.einsum("td,edf->tef", xf, p["w_gate"]))
    h = h * jnp.einsum("td,edf->tef", xf, p["w_up"])
    y = jnp.einsum("tef,efd->ted", h, p["w_down"])
    out = jnp.einsum("ted,te->td", y.astype(jnp.float32), w)
    return out.astype(x.dtype).reshape(B, S, D), aux
