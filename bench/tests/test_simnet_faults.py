"""The SimNet cell rehearsed on the CPU (tiny widths, ``bench/run.py
--rehearse``): a sound run is correct, and each fault planted in the
program it measures turns ``correct`` false: a context that never retires,
the context's timing features dropped, and warm-up cycles counted."""
import argparse
import time

import jax.numpy as jnp
import pytest

from bench import harness

CELL = "simnet-c3.sim-long"
# a seed whose model advances the clock: where every fetch latency rounds
# to 0, nothing retires in a sound run either, and the context is full
SEED = 7


@pytest.fixture(autouse=True)
def fresh_steps():
    from repro.engine import clear_step_cache

    clear_step_cache()
    yield
    clear_step_cache()


def run(seed: int = SEED) -> dict:
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=0.5, trace=0, rehearse=True)
    return harness.run_cell(args, time.perf_counter(), require_chip=False)


def test_sound_run_is_correct():
    res = run()
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


def test_control_stands_apart():
    """The control (the reference with fp8 matmuls, read against the
    full-precision reference) fails the cell's limit where the program
    passes it, by more than three times the program's reading."""
    cell = harness.Cell.load(CELL, rehearse=True)
    run_ = harness.Run(cell, SEED, rehearse=True)
    drv = harness.load_module("drivers", cell.traffic["driver"]).Driver(run_)
    drv.setup()
    win = harness.closed_loop(drv, run_, 0.5)
    drv.release()
    got = drv.check(win)["latency_gap"]
    control = drv.control()["latency_gap"]
    limit = cell.traffic["limits"]["latency_gap"]
    assert got <= limit < control and control > max(3 * got, 1e-3), (got, control)


def test_context_never_retires(monkeypatch):
    import repro.core.simnet as S

    monkeypatch.setattr(S, "in_flight", lambda R, T: R >= 0)
    res = run()
    assert not res["correct"] and res["checks"]["latency_gap"]["value"] > res["checks"]["latency_gap"]["limit"]


def test_timing_features_dropped(monkeypatch):
    import repro.core.simnet as S

    timing = S.timing_features
    monkeypatch.setattr(S, "timing_features", lambda T, F, C: jnp.zeros_like(timing(T, F, C)))
    res = run()
    assert not res["correct"] and res["checks"]["latency_gap"]["value"] > res["checks"]["latency_gap"]["limit"]


def test_warmup_counted(monkeypatch):
    import repro.engine.sequential as Q

    monkeypatch.setattr(Q, "counted_from", lambda cfg: 0)
    res = run()
    assert not res["correct"] and res["checks"]["cpi_gap"]["value"] > 0
