"""Validate the loop-corrected HLO analyzer against ground truth."""
import jax
import jax.numpy as jnp
import pytest

from benchmarks.hlo_analysis import analyze

D = 256
ITERS = 10
FLOPS_ONE_MATMUL = 2 * 8 * D * D


def _scan_fn(x, W):
    def body(h, _):
        return h @ W, None
    h, _ = jax.lax.scan(body, x, None, length=ITERS)
    return h


def _unrolled_fn(x, W):
    for _ in range(ITERS):
        x = x @ W
    return x


@pytest.fixture(scope="module")
def lowered():
    W = jnp.zeros((D, D), jnp.float32)
    x = jnp.zeros((8, D), jnp.float32)
    scan = jax.jit(lambda x: _scan_fn(x, W)).lower(x).compile()
    unroll = jax.jit(lambda x: _unrolled_fn(x, W)).lower(x).compile()
    return scan, unroll


class TestLoopCorrection:
    def test_xla_cost_analysis_undercounts_scans(self, lowered):
        """The motivating defect: XLA counts a while body once."""
        scan, unroll = lowered
        assert scan.cost_analysis()["flops"] == pytest.approx(
            FLOPS_ONE_MATMUL, rel=0.01)
        assert unroll.cost_analysis()["flops"] == pytest.approx(
            ITERS * FLOPS_ONE_MATMUL, rel=0.01)

    def test_analyzer_is_loop_exact(self, lowered):
        """Our analyzer multiplies bodies by known_trip_count."""
        scan, unroll = lowered
        a_scan = analyze(scan.as_text())
        a_unroll = analyze(unroll.as_text())
        assert a_scan["flops"] == pytest.approx(
            ITERS * FLOPS_ONE_MATMUL, rel=0.01)
        assert a_unroll["flops"] == pytest.approx(
            ITERS * FLOPS_ONE_MATMUL, rel=0.01)

    def test_bytes_scale_with_trip_count(self, lowered):
        """Loop-corrected bytes are the same order as the unrolled twin
        (the lowerings legitimately differ: the scan carries loop state
        the unrolled version fuses away) — and nowhere near the 10x
        undercount the uncorrected analysis would give."""
        scan, unroll = lowered
        a_scan = analyze(scan.as_text())
        a_unroll = analyze(unroll.as_text())
        ratio = a_scan["bytes_hbm"] / a_unroll["bytes_hbm"]
        assert 0.5 < ratio < 2.5

    def test_collectives_counted_per_kind(self):
        hlo = """
HloModule test

ENTRY %main (p: f32[16,64]) -> f32[16,64] {
  %p = f32[16,64]{1,0} parameter(0)
  %ar = f32[16,64]{1,0} all-reduce(%p), to_apply=%add
  ROOT %ag = f32[16,64]{1,0} all-gather(%ar), dimensions={0}
}
"""
        a = analyze(hlo)
        nbytes = 16 * 64 * 4
        assert a["collective_bytes"]["all-reduce"] == 2 * nbytes
        assert a["collective_bytes"]["all-gather"] == nbytes
