"""A run with the timed path broken underneath must come out not correct.

Each test drives a whole rehearsal run (tiny widths, CPU) through the
harness, past its look for a chip, with one fault planted in the program
it measures: a step that returns its state unchanged, half of each batch
left out, the exchange between chips left out, an answer altered where it
is produced.  A sound run of every cell comes out correct.
"""
import argparse
import time

import jax
import pytest

from bench import harness
from bench.reference import model as ref

# the four-chip sweep is not a cell of BENCHMARK.json yet; its driver is
# rehearsed from this entry (four virtual devices, conftest.py)
SWEEP = {"name": "dse32.sweep-4chip", "config": "tao-paper-dse32", "traffic": "sweep-4chip", "chips": 4}
SIM = ["paper.sim-long", "paper.sim-intervals", "dse32.sweep-4chip"]
ALL = SIM + ["paper.transfer-train"]


@pytest.fixture(autouse=True)
def fresh_steps():
    from repro.engine import clear_step_cache
    from repro.train.trainer import clear_train_step_cache

    clear_step_cache()
    clear_train_step_cache()
    yield
    clear_step_cache()
    clear_train_step_cache()


def run(workload: str, seed: int = 11) -> dict:
    args = argparse.Namespace(workload=workload, seed=seed, seconds=0.5, trace=0, rehearse=True,
                              entry=SWEEP if workload == SWEEP["name"] else None)
    return harness.run_cell(args, time.perf_counter(), require_chip=False)


@pytest.mark.parametrize("workload", ALL)
def test_sound_run_is_correct(workload):
    res = run(workload)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("workload", SIM)
def test_answer_altered_where_produced(monkeypatch, workload):
    import repro.engine.runner as R

    forward = R.tao_forward

    def altered(params, batch, cfg):
        # off by one bucket where the latency is decoded
        out = forward(params, batch, cfg)
        reps = jax.numpy.asarray(ref.LAT_REPS)
        up = jax.numpy.minimum(jax.numpy.argmax(out["fetch_lat_logits"], -1) + 1, len(reps) - 1)
        return {**out, "fetch_lat": reps[up]}

    monkeypatch.setattr(R, "tao_forward", altered)
    assert not run(workload)["correct"]


@pytest.mark.parametrize("workload", SIM)
def test_half_batch_left_out(monkeypatch, workload):
    import repro.engine.runner as R

    batches = R.StreamingEngine._fused_batches

    def half(self, cols, w_eff, count):
        for b in batches(self, cols, w_eff, count):
            v = b["valid"]
            yield {**b, "valid": v.at[v.shape[0] // 2:].set(0.0)}

    monkeypatch.setattr(R.StreamingEngine, "_fused_batches", half)
    assert not run(workload)["correct"]


@pytest.mark.parametrize("workload", SIM)
def test_step_returns_state_unchanged(monkeypatch, workload):
    import repro.engine.runner as R

    get = R.StreamingEngine._get_step

    def frozen(self, w_eff):
        entry = get(self, w_eff)
        shim = R._CachedStep()
        shim.fn = jax.jit(lambda params, carry, batch: (carry, {}))
        shim.compiles = entry.compiles
        return shim

    monkeypatch.setattr(R.StreamingEngine, "_get_step", frozen)
    assert not run(workload)["correct"]


def test_exchange_between_chips_left_out(monkeypatch):
    from repro.engine.plan import AxisContext

    def local(self, x):
        # only chip 0's partial survives: the other chips' parts never arrive
        if not self.axes:
            return x
        return jax.lax.psum(jax.numpy.where(self.shard_index() == 0, x, 0), self.axes)

    monkeypatch.setattr(AxisContext, "psum", local)
    assert not run("dse32.sweep-4chip")["correct"]


def test_train_step_returns_state_unchanged(monkeypatch):
    import repro.core.transfer as T

    monkeypatch.setattr(T, "adamw_update", lambda params, grads, opt, cfg: (params, opt, 0.0))
    assert not run("paper.transfer-train")["correct"]


def test_train_half_batch_left_out(monkeypatch):
    import repro.core.transfer as T

    loss = T.multi_metric_loss

    def half(preds, labels, weights=None):
        cut = {k: v[: v.shape[0] // 2] for k, v in preds.items()}
        return loss(cut, {k: v[: v.shape[0] // 2] for k, v in labels.items()}, weights)

    monkeypatch.setattr(T, "multi_metric_loss", half)
    assert not run("paper.transfer-train")["correct"]


def test_no_chip_no_result():
    args = argparse.Namespace(workload="paper.sim-long", seed=1, seconds=1.0, trace=0, rehearse=False)
    assert harness.run_cell(args, time.perf_counter(), require_chip=True) is None
