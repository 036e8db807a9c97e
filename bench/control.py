#!/usr/bin/env python3
"""The readings the limits of ``correct`` are set from, in one process.

    python3 bench/control.py --workload <name> --seconds <s> \
        --seeds 11,12,… --control-seeds 21,22,23

For each of ``--seeds`` it runs the cell as ``bench/run.py`` does (set-up
from the seed, a window of ``--seconds`` at the cell's own load, the
comparison with the plain reference) and prints the numbers compared: the
program's readings, whose largest is a limit's lower reading. For each of
``--control-seeds`` it puts the control in the program's place, the plain
reference with its products at three bf16 passes (``Precision.HIGH``, the
precision below the configuration's ``HIGHEST``), and prints the same
numbers: the control's readings, whose smallest is the upper reading. The
last line is a JSON object with both lists. The benchmark's own runs never
run this; it runs on the chip, like them.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
if sys.path and os.path.abspath(sys.path[0] or ".") == _HERE:
    sys.path.pop(0)
sys.path.insert(0, os.path.dirname(_HERE))

from bench import run  # noqa: E402  (run puts src/ on the path)
from bench import cells  # noqa: E402


def readings(spec, seed: int, seconds: float, variant: str) -> dict:
    """The numbers compared in one run of ``variant`` on ``seed``."""
    cell = run.make_cell(spec, seed, seconds, False, variant)
    drv = spec.driver
    state = drv.setup(cell)
    res = drv.window(state, seconds)
    lost = drv.counts(res)[2]
    numbers = drv.check(state, res)
    if lost:
        numbers["lost"] = float(lost)
    return numbers


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    spec = cells.resolve(args.workload)
    run.enable_compile_cache()
    if run.tpu_devices(spec.chips) is None:
        return 2
    out = {"workload": args.workload, "program": [], "control": []}
    for variant, seeds in (("program", args.seeds),
                           ("control", args.control_seeds)):
        for s in [int(x) for x in seeds.split(",") if x]:
            t0 = time.perf_counter()
            nums = readings(spec, s, args.seconds, variant)
            out[variant].append({"seed": s, **nums})
            print(f"{variant} seed={s} {nums} "
                  f"({time.perf_counter() - t0:.1f} s)", flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
