"""Operations one prefill step of a configuration needs.

Counted from the configuration file and the step's shape alone, as
multiply-adds times two, by the ``prefill_flops`` of the architecture
the file names (``arch/<arch>.py``, found by `architectures`): a dict
of ``layers``, ``attention`` and ``head`` operations and their
``total``, which ``step_mfu`` reads. Only matrix products count.
"""
from __future__ import annotations

from chipbench import architectures


def prefill_flops(spec: dict, batch: int, seq: int, arch=None) -> dict:
    """By `arch`, or by default the architecture `spec` names."""
    return architectures.of(spec, arch).arch.prefill_flops(spec, batch, seq)
