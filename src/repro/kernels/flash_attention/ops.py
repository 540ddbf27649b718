"""Public wrapper: (B, S, H, hd) layout in/out, kernel or oracle.

The caller chooses both the path (`use_kernel`) and, for the kernel,
whether it runs compiled for the TPU or in the Pallas interpreter
(`interpret`); nothing here looks at the backend it finds.
"""
from __future__ import annotations

from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import attention_ref


def mha(q, k, v, *, use_kernel: bool, interpret: bool = False,
        causal: bool = True, window: int = 0, block_q: int = 128,
        block_k: int = 128):
    """q: (B, S, H, hd); k, v: (B, S, K, hd) -> (B, S, H, hd)."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if use_kernel:
        ot = flash_attention(qt, kt, vt, causal=causal, window=window,
                             block_q=block_q, block_k=block_k,
                             interpret=interpret)
    else:
        ot = attention_ref(qt, kt, vt, causal=causal, window=window)
    return ot.transpose(0, 2, 1, 3)
