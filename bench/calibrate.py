"""The readings a cell's limits are set from (``run.py --calibrate K``).

One process, one set-up; then for each of K seeds from ``--seed``: that
seed's weights and traffic, a window of ``--seconds`` (0 for training,
whose readings need none), the program's state released, and the check.
The first three seeds also read the control (the driver's: the program's
int8 path, or the reference in fp8) and any planted faults the driver has.  Each seed is
one JSON line; the last line is the summary: per number, the largest
program reading and the smallest control and fault readings.
"""
from __future__ import annotations

import json
import sys

from bench import harness


def main(args) -> int:
    import jax

    cell = harness.Cell.load(args.workload, rehearse=args.rehearse)
    devs = jax.devices()
    if not args.rehearse and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        print("no TPU for this cell", file=sys.stderr)
        return 2
    from repro.engine import enable_persistent_cache

    enable_persistent_cache()
    run = harness.Run(cell, args.seed, args.rehearse)
    drv = harness.load_module("drivers", cell.traffic["driver"]).Driver(run)
    drv.setup()
    prog, ctrl, faults = {}, {}, {}
    for j in range(args.calibrate):
        seed = args.seed + j
        if j:
            drv.reseed(seed)
        win = harness.closed_loop(drv, run, args.seconds)
        drv.release()
        row = {"seed": seed, "requests": len(win.requests), "failed": win.failed,
               "program": drv.check(win)}
        if getattr(drv, "detail", None):
            row["detail"] = drv.detail
        if j < 3:
            row["control"] = drv.control()
            row["faults"] = drv.faults()
            for k, v in row["control"].items():
                ctrl[k] = min(ctrl.get(k, float("inf")), v)
            for f, vals in row["faults"].items():
                for k, v in vals.items():
                    faults[f"{f}.{k}"] = min(faults.get(f"{f}.{k}", float("inf")), v)
        for k, v in row["program"].items():
            prog[k] = max(prog.get(k, float("-inf")), v)
        print(json.dumps(row), flush=True)
    print(json.dumps({"summary": {"program_max": prog, "control_min": ctrl,
                                  "fault_min": faults}}), flush=True)
    return 0
