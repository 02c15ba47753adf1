"""The control must come out not correct: in the program's place, the
program's own int8 path (simulation) or the plain reference with fp8
matmuls (training), the precision below the configuration's one-pass
bfloat16 matmuls.  On the chip at the cell's own size its readings
(``bench/run.py --calibrate``) fail the cell's limits; they are in PERF.md.
Here, on the CPU at tiny widths, where the program computes in float32 and
meets the reference to rounding, the same path is driven end to end on
three seeds: the program passes the cell's limits, and the control reads
at least three times the program's reading (and over 1e-3) in one of the
compared numbers."""
import pytest

from bench import harness
from bench.tests.test_faults import SWEEP

CELLS = ["paper.sim-long", "paper.sim-intervals", "paper.transfer-train", "dse32.sweep-4chip"]


@pytest.mark.parametrize("workload", CELLS)
def test_control_stands_apart(workload):
    cell = harness.Cell.load(workload, rehearse=True,
                             entry=SWEEP if workload == SWEEP["name"] else None)
    limits = cell.traffic["limits"]
    run = harness.Run(cell, 31, rehearse=True)
    drv = harness.load_module("drivers", cell.traffic["driver"]).Driver(run)
    drv.setup()
    for seed in (31, 32, 33):
        if seed != 31:
            drv.reseed(seed)
        win = harness.closed_loop(drv, run, 0.5 if cell.traffic["driver"] != "train" else 0.0)
        drv.release()
        readings = drv.check(win)
        assert all(readings[k] <= lim for k, lim in limits.items()), readings
        control = drv.control()
        assert any(control[k] > max(3 * readings[k], 1e-3) for k in limits if k in control), (
            seed, control, readings)
