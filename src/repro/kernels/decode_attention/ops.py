"""Public wrapper for decode attention, (B, 1, H, hd) layout.

The caller chooses the kernel or the oracle (`use_kernel`) and whether
the kernel runs in the Pallas interpreter (`interpret`).
"""
from __future__ import annotations

from repro.kernels.decode_attention.kernel import flash_decode
from repro.kernels.decode_attention.ref import decode_ref


def decode_mha(q, k_cache, v_cache, slot_pos, pos, *, use_kernel: bool,
               interpret: bool = False, window: int = 0, block_k: int = 256):
    """q: (B, 1, H, hd); caches: (B, W, K, hd) -> (B, 1, H, hd)."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k_cache.transpose(0, 2, 1, 3)
    vt = v_cache.transpose(0, 2, 1, 3)
    if use_kernel:
        ot = flash_decode(qt, kt, vt, slot_pos, pos, window=window,
                          block_k=block_k, interpret=interpret)
    else:
        ot = decode_ref(qt, kt, vt, slot_pos, pos, window=window)
    return ot.transpose(0, 2, 1, 3)
