"""Flash-decoding kernel: one query token vs a (ring) KV cache.

Decode attention is memory-bound: the whole KV cache streams through
VMEM once per step while compute is a single (1 x hd) @ (hd x W) row.
The kernel therefore splits the cache width W into kv blocks on the
innermost (sequential) grid axis and carries the online-softmax state
(m, l, acc) in VMEM scratch — the TPU shape of GPU flash-decoding's
KV-split trick; on a real pod the q-head grid axis is parallel across
cores so all MXU/VPU lanes stay fed while HBM streams the cache.

Ring-cache semantics come in via ``slot_pos`` (absolute position stored
in each slot, -1 = empty): masking handles bootstrap (empty slots),
causality (slot <= pos) and sliding windows (pos - slot < window) in
one compare — identical to the model-layer reference.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, slot_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, scale: float, block_k: int,
                   window: int):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (1, hd)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)                  # (bk, hd)
    slots = slot_ref[0]                                  # (1, bk) int32
    pos = pos_ref[pl.program_id(0)]                      # scalar (SMEM)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (1, bk)
    valid = (slots >= 0) & (slots <= pos)
    if window > 0:
        valid &= (pos - slots) < window
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]                                  # (1, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = (acc_ref[...] * alpha
                    + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32))
    m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _write():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)[None, None]


@functools.partial(jax.jit, static_argnames=("window", "block_k",
                                             "interpret"))
def flash_decode(q, k_cache, v_cache, slot_pos, pos, *, window: int = 0,
                 block_k: int = 256, interpret: bool = True):
    """q: (B, H, 1, hd); k_cache, v_cache: (B, K, W, hd);
    slot_pos: (B, W) int32; pos: (B,) int32. Returns (B, H, 1, hd).

    ``pos`` rides in SMEM by scalar prefetch, and ``slot_pos`` is viewed
    as (B, 1, W) so each block's last two dims are (1, block_k): both
    meet the TPU's block tiling rules at any batch size."""
    B, H, _, hd = q.shape
    K, W = k_cache.shape[1], k_cache.shape[2]
    G = H // K
    scale = 1.0 / math.sqrt(hd)

    block_k = min(block_k, W)
    pad_k = (-W) % block_k
    if pad_k:
        padw = ((0, 0), (0, 0), (0, pad_k), (0, 0))
        k_cache = jnp.pad(k_cache, padw)
        v_cache = jnp.pad(v_cache, padw)
        slot_pos = jnp.pad(slot_pos, ((0, 0), (0, pad_k)),
                           constant_values=-1)
    nk = k_cache.shape[2] // block_k

    kernel = functools.partial(_decode_kernel, scale=scale,
                               block_k=block_k, window=window)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                               # pos
            grid=(B, H, nk),
            in_specs=[
                pl.BlockSpec((1, 1, 1, hd),
                             lambda b, h, ki, pos: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, block_k, hd),
                             lambda b, h, ki, pos, G=G: (b, h // G, ki, 0)),
                pl.BlockSpec((1, 1, block_k, hd),
                             lambda b, h, ki, pos, G=G: (b, h // G, ki, 0)),
                pl.BlockSpec((1, 1, block_k),
                             lambda b, h, ki, pos: (b, 0, ki)),  # slots
            ],
            out_specs=pl.BlockSpec((1, 1, 1, hd),
                                   lambda b, h, ki, pos: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, hd), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, H, 1, hd), q.dtype),
        interpret=interpret,
    )(pos.astype(jnp.int32), q, k_cache, v_cache,
      slot_pos.astype(jnp.int32)[:, None, :])
    return out
