"""Public wrapper for the selective scan.

The caller chooses the kernel or the oracle (`use_kernel`) and whether
the kernel runs in the Pallas interpreter (`interpret`).
"""
from __future__ import annotations

from repro.kernels.ssm_scan.kernel import ssm_scan
from repro.kernels.ssm_scan.ref import ssm_scan_ref


def selective_scan(dt, xr, Bmat, Cmat, A, h0, *, use_kernel: bool,
                   interpret: bool = False, chunk: int = 128,
                   block_d: int = 128):
    if use_kernel:
        return ssm_scan(dt, xr, Bmat, Cmat, A, h0, chunk=chunk,
                        block_d=block_d, interpret=interpret)
    return ssm_scan_ref(dt, xr, Bmat, Cmat, A, h0)
