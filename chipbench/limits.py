#!/usr/bin/env python3
"""Read the numbers a cell's limits are set from, on the chip.

From the root of a checkout, on a machine with the cell's chips::

    python3 chipbench/limits.py --workload <cell> --seeds 11,12,13 --seconds 30

In one process, for each seed in turn: the cell's set-up and a window
of ``--seconds`` (long enough to complete as many answers as a run
checks), then the check, which also runs the control on the same
prompts: the reference of the configuration's own architecture
(``reference/<arch>.py``) in float8. Each seed prints one JSON line
with the program's numbers and the control's; the last line
gives, per number, the largest the program read (the lower reading) and
the smallest the control read (the upper one). The benchmark's own runs
never run the control.
"""
import time

T0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# the TPU runtime's own logs would go to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from chipbench import harness  # noqa: E402


def main(argv) -> int:
    p = argparse.ArgumentParser(prog="chipbench/limits.py")
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    try:
        _, cell, device = harness.start(
            ["--workload", args.workload, "--seed", "0", "--seconds", "0"])
    except harness.Refused as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return e.code
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        run = harness.serve(cell, seed, args.seconds, False, t0,
                            log=lambda *_: None)
        t = time.monotonic()
        res = harness.check(cell, seed, run, control=True)
        row = dict(res, seed=seed, setup_s=run["setup_s"],
                   attempted=len(run["invocations"]),
                   failed=sum(not r["ok"] for r in run["invocations"]),
                   check_s=time.monotonic() - t,
                   memory_peak_bytes=run["memory_peak_bytes"],
                   device=device)
        del run
        print(json.dumps(row), flush=True)
        rows.append(row)
    lower = {n: max(r["program"][n] for r in rows) for n in rows[0]["program"]}
    upper = {n: min(r["control"][n] for r in rows) for n in rows[0]["control"]}
    print(json.dumps({"cell": cell.name, "lower": lower, "upper": upper}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
