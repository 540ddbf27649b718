#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

From the root of a checkout, on a machine with the chips the cell asks
for::

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

See `chipbench.harness` for what a run does and prints. Without a TPU,
or without the program's code beside ``chipbench/``, it exits non-zero
and prints no result.
"""
import time

T0 = time.monotonic()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# the TPU runtime's own logs would go to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T0))
