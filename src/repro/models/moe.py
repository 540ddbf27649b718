"""Mixture-of-Experts FFN with top-k routing.

Two dispatch implementations:

* ``sorted`` (default, production): tokens are sorted by expert id and
  scattered into an (E, C, D) capacity buffer, experts run as one batched
  einsum, results are gathered back and combined with the gate weights.
  Compute overhead over the active-FLOPs ideal is just the capacity
  factor (default 1.25x) — no (T, E, C) one-hot dispatch tensors.
* ``dense``: every token through every expert with mask-combine. Exact
  (dropless) but E/k times the FLOPs — used as the correctness oracle in
  tests and for tiny decode batches.

Experts are sharded over the ``model`` mesh axis (expert parallelism);
GSPMD inserts the token all-to-all around the capacity buffer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init, shard_hint, split_rngs


def init_moe(rng, cfg, dtype):
    D, F, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    r = split_rngs(rng, 4)
    return {
        "router": dense_init(r[0], (D, E), 0, jnp.float32),
        "w_gate": dense_init(r[1], (E, D, F), 1, dtype),
        "w_up": dense_init(r[2], (E, D, F), 1, dtype),
        "w_down": dense_init(r[3], (E, F, D), 1, dtype),
    }


def _route(p, cfg, xf):
    """Router in fp32. xf: (T, D) -> gates (T, k), idx (T, k), aux_loss."""
    logits = xf.astype(jnp.float32) @ p["router"]       # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balancing auxiliary loss
    E = cfg.num_experts
    me = probs.mean(axis=0)                             # mean router prob
    ce = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    ce = ce / jnp.maximum(ce.sum(), 1.0)
    aux = E * jnp.sum(me * ce) * cfg.router_aux_loss
    return gates, idx, aux


def _experts_ffn(p, buf):
    """buf: (E, C, D) -> (E, C, D) through per-expert SwiGLU."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    return jnp.einsum("ecf,efd->ecd", h, p["w_down"])


def moe_sorted(p, cfg, x):
    """Sort-based capacity-C dispatch. x: (B, S, D) -> (out, aux_loss)."""
    B, S, D = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    T = B * S
    xf = shard_hint(x.reshape(T, D), "batch", None)
    gates, idx, aux = _route(p, cfg, xf)

    A = T * k                                           # assignments
    cap = max(int(A / E * cfg.capacity_factor), 8)

    flat_e = idx.reshape(A)
    sort_i = jnp.argsort(flat_e)                        # stable
    se = flat_e[sort_i]                                 # sorted expert ids
    tok = sort_i // k                                   # source token
    # slot within expert group = rank - first rank of that expert
    gstart = jnp.searchsorted(se, jnp.arange(E))
    slot = jnp.arange(A) - gstart[se]
    keep = slot < cap

    buf = jnp.zeros((E, cap, D), x.dtype)
    buf = buf.at[se, slot].set(
        jnp.where(keep[:, None], xf[tok], 0), mode="drop")
    # Replicated-expert case (E does not divide `model`, e.g. Mixtral):
    # pin capacity to the data axes so the FFN never gathers the full
    # (E, cap, D) buffer. True-EP archs (qwen3: E=128) keep GSPMD's own
    # expert-sharded layout — hinting them regressed 10x (SPerf log).
    mesh = jax.sharding.get_abstract_mesh()
    model_n = dict(mesh.shape).get("model", 1) if mesh.axis_names else 1
    if model_n > 1 and E % model_n != 0:
        buf = shard_hint(buf, None, "batch", None)
        out_buf = _experts_ffn(p, buf)                  # (E, cap, D)
        out_buf = shard_hint(out_buf, None, "batch", None)
    else:
        out_buf = _experts_ffn(p, buf)

    contrib = out_buf[se, jnp.minimum(slot, cap - 1)]   # (A, D)
    contrib = jnp.where(keep[:, None], contrib, 0)
    # back to original assignment order, weight by gates, sum over k
    y = jnp.zeros((A, D), x.dtype).at[sort_i].set(contrib)
    y = (y.reshape(T, k, D) * gates[..., None].astype(x.dtype)).sum(axis=1)
    return y.reshape(B, S, D), aux


def moe_dense(p, cfg, x):
    """Dropless masked-dense dispatch (oracle; E/k x FLOPs)."""
    B, S, D = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    T = B * S
    xf = x.reshape(T, D)
    gates, idx, aux = _route(p, cfg, xf)
    # combine weight per (token, expert)
    w = jnp.zeros((T, E), jnp.float32)
    w = w.at[jnp.arange(T)[:, None], idx].add(gates)
    h = jax.nn.silu(jnp.einsum("td,edf->tef", xf, p["w_gate"]))
    h = h * jnp.einsum("td,edf->tef", xf, p["w_up"])
    y = jnp.einsum("tef,efd->ted", h, p["w_down"])
    out = jnp.einsum("ted,te->td", y.astype(jnp.float32), w)
    return out.astype(x.dtype).reshape(B, S, D), aux


def moe_local(p, cfg, x):
    """Shard-local dispatch (beyond-paper, SPerf cell B iteration 2).

    The global sort in `moe_sorted` becomes a distributed sort under
    GSPMD — the dominant collective for replicated-expert archs (E <
    model axis, e.g. Mixtral's 8). Tokens never *need* to leave their
    data shard when experts are replicated over it, so we `shard_map`
    the dispatch over the batch axes (manual) and leave the expert FFN
    to GSPMD on the model axis (auto): each shard sorts only its local
    T/shards tokens into a local capacity buffer. No global sort, no
    dispatch collectives; the load-balance statistics are pmean'd.
    """
    mesh = jax.sharding.get_abstract_mesh()
    fsdp = tuple(a for a in (mesh.axis_names or ())
                 if a in ("pod", "data"))
    n = 1
    for a in fsdp:
        n *= mesh.shape[a]
    if not fsdp or x.shape[0] % n != 0:
        return moe_sorted(p, cfg, x)        # e.g. decode with B=1

    from jax.sharding import PartitionSpec as P

    def local(xb, pb):
        y, aux = moe_sorted(pb, cfg, xb)
        return y, jax.lax.pmean(aux, fsdp)

    return jax.shard_map(
        local,
        in_specs=(P(fsdp, None, None), P()),
        out_specs=(P(fsdp, None, None), P()),
        axis_names=set(fsdp),
    )(x, p)


def moe_layer(p, cfg, x):
    if cfg.moe_impl == "dense":
        return moe_dense(p, cfg, x)
    if cfg.moe_impl == "local":
        return moe_local(p, cfg, x)
    return moe_sorted(p, cfg, x)
