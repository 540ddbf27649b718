"""Compile the served model and the Pallas kernels for a TPU v5e chip.

Nothing runs: the TPU compiler, installed beside JAX, compiles for a
described ``v5e:2x2`` topology whose devices are not attached. That
catches what interpret mode and the CPU never show — a kernel block the
TPU's tiling refuses, a primitive Mosaic cannot lower, a step that does
not fit the chip's 16 GB. The topology is described inside a fixture,
never at import, so every xdist worker collects the same tests and only
the one that runs this file loads the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import registry
from repro.configs.granite_8b import CHIP
from repro.kernels.decode_attention import flash_decode
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssm_scan import ssm_scan
from repro.models import serving

#: one v5e chip holds 16 GB; leave a margin for the runtime's own use
STEP_BUDGET_BYTES = 15e9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _step_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)


@pytest.mark.parametrize("batch,seq", [(32, 512), (1, 2048), (8, 2048)])
def test_chip_prefill_fits(one_chip, batch, seq):
    b = serving.bundle(CHIP)
    params = _on(one_chip, b["structs"]["params"])
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                                  sharding=one_chip)
    compiled = b["prefill"].lower(params, {"tokens": tokens}).compile()
    assert _step_bytes(compiled) < STEP_BUDGET_BYTES


def test_chip_decode_step_fits(one_chip):
    b = serving.bundle(CHIP)
    st = b["structs"]
    assert st["decode_cache"]["k"].shape[1:3] == (8, 2048)
    compiled = b["decode"].lower(
        _on(one_chip, st["params"]), _on(one_chip, st["decode_cache"]),
        _on(one_chip, st["step_token"])).compile()
    assert _step_bytes(compiled) < STEP_BUDGET_BYTES


def _kernel_compiles(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles_at_granite_widths(one_chip):
    H, K, hd, S = CHIP.num_heads, CHIP.num_kv_heads, CHIP.head_dim, 2048
    q = jax.ShapeDtypeStruct((1, H, S, hd), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, K, S, hd), jnp.bfloat16, sharding=one_chip)
    _kernel_compiles(lambda q, k, v: flash_attention(q, k, v,
                                                     interpret=False),
                     q, kv, kv)


def test_flash_decode_compiles_at_granite_widths(one_chip):
    H, K, hd = CHIP.num_heads, CHIP.num_kv_heads, CHIP.head_dim
    B, W = 8, 2048
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    _kernel_compiles(
        lambda q, k, v, sp, p: flash_decode(q, k, v, sp, p, interpret=False),
        S((B, H, 1, hd), jnp.bfloat16), S((B, K, W, hd), jnp.bfloat16),
        S((B, K, W, hd), jnp.bfloat16), S((B, W), jnp.int32),
        S((B,), jnp.int32))


def test_ssm_scan_compiles_at_falcon_mamba_widths(one_chip):
    cfg = registry.get("falcon-mamba-7b")
    di, N, S = cfg.d_inner, cfg.ssm_state, 2048
    f32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                             sharding=one_chip)
    _kernel_compiles(
        lambda dt, x, b, c, a, h: ssm_scan(dt, x, b, c, a, h,
                                           interpret=False),
        f32((1, S, di)), f32((1, S, di)), f32((1, S, N)), f32((1, S, N)),
        f32((di, N)), f32((1, di, N)))
