"""The served step's operations (`flops.py`) times its runs in the trace,
over its device seconds times the chip's bf16 peak, in percent."""
from chipbench import readers


def read(run):
    return readers.step_mfu(run)
