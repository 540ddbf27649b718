"""Chunked Mamba-1 selective-scan kernel.

The recurrence h_t = exp(dt_t * A) h_{t-1} + dt_t B_t x_t is sequential
in t but embarrassingly parallel over (batch, d_inner). TPU blocking:

* grid = (B, d_inner / block_d, S / chunk) with the *chunk* axis
  innermost and sequential — the carried state h (block_d, N) lives in
  VMEM scratch across chunk steps, so HBM sees each input element
  exactly once (the memory-roofline optimum for this op);
* within a chunk the (chunk, block_d, N) discretized tensors exist only
  in VMEM/registers — never in HBM (this bound is what forced the
  jnp reference to the same chunked structure);
* channels are blocked at block_d lanes so A/dt/x tiles are
  (chunk, block_d) VPU-aligned; N (=16) rides the sublane dim.

The in-chunk scan here is an exact fori_loop recurrence (time steps are
VPU element-wise ops, no MXU work) — the production variant would swap
in the log-segsum associative form for more ILP, with identical
interface and semantics.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssm_kernel(dt_ref, xr_ref, b_ref, c_ref, a_ref, h0_ref, y_ref,
                hout_ref, h_ref, *, chunk: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)       # (bd, N)

    a = a_ref[...].astype(jnp.float32)                   # (bd, N)
    bd = a.shape[0]

    def step(t, h):
        # one time step's rows, read through ref slices: Mosaic lowers a
        # dynamic sublane offset on a ref, not on a loaded value
        row = pl.ds(t, 1)
        dt_t = dt_ref[0, row, :].astype(jnp.float32).reshape(bd, 1)
        xr_t = xr_ref[0, row, :].astype(jnp.float32).reshape(bd, 1)
        b_t = b_ref[0, row, :].astype(jnp.float32)        # (1, N)
        c_t = c_ref[0, row, :].astype(jnp.float32)        # (1, N)
        h = h * jnp.exp(dt_t * a) + (dt_t * xr_t) * b_t   # (bd, N)
        y_t = jnp.sum(h * c_t, axis=-1, keepdims=True)    # (bd, 1)
        y_ref[0, row, :] = y_t.reshape(1, bd).astype(y_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, chunk, step, h_ref[...])

    @pl.when(ci == nc - 1)
    def _write_state():
        hout_ref[...] = h_ref[...][None]


@functools.partial(jax.jit, static_argnames=("chunk", "block_d",
                                             "interpret"))
def ssm_scan(dt, xr, Bmat, Cmat, A, h0, *, chunk: int = 128,
             block_d: int = 128, interpret: bool = True):
    """Selective scan, emitting y and the final state.

    dt, xr: (B, S, di) fp32; Bmat, Cmat: (B, S, N) fp32;
    A: (di, N) fp32 (negative); h0: (B, di, N) fp32.
    Returns (y (B, S, di) fp32, h_final (B, di, N) fp32).
    """
    B, S, di = dt.shape
    N = A.shape[1]
    chunk = min(chunk, S)
    block_d = min(block_d, di)
    pad_s = (-S) % chunk
    if pad_s:
        pad3 = ((0, 0), (0, pad_s), (0, 0))
        dt = jnp.pad(dt, pad3)
        xr = jnp.pad(xr, pad3)
        Bmat = jnp.pad(Bmat, pad3)
        Cmat = jnp.pad(Cmat, pad3)
    assert di % block_d == 0, (di, block_d)
    nc = dt.shape[1] // chunk
    nd = di // block_d

    grid = (B, nd, nc)
    kernel = functools.partial(_ssm_kernel, chunk=chunk)

    y, h_final = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, chunk, N), lambda b, d, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, d, c: (b, c, 0)),
            pl.BlockSpec((block_d, N), lambda b, d, c: (d, 0)),
            pl.BlockSpec((1, block_d, N), lambda b, d, c: (b, d, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, block_d, N), lambda b, d, c: (b, d, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, nc * chunk, di), jnp.float32),
            jax.ShapeDtypeStruct((B, di, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_d, N), jnp.float32)],
        interpret=interpret,
    )(dt, xr, Bmat, Cmat, A, h0)
    return y[:, :S], h_final
