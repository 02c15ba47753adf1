"""The readers of the program's own spans (``bench/spans.py``): by hand on
made-up traces, and on recorded chip traces that hold program spans
(``data/spans_*.json``, written by ``python bench/spans.py --record``)."""
import glob
import json
import os

import pytest

from bench import harness, spans
from bench.tests.test_flops import config
from bench.tests.test_reduce import PEAK, made_up

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000


def ms(evs):
    return [(s * MS, e * MS, *rest) for s, e, *rest in evs]


def read(name, t):
    return harness.load_module("metrics", name).read(t)


def sim_trace():
    """``test_reduce.made_up`` (busy 10-25, 40-50, 70-90 ms; requests 6-55
    and 60-95) with the program's spans of two simulate calls, and a third
    call that leaves the window."""
    t = made_up()
    t.program = sorted(ms([
        (7, 54, "tao/engine.simulate", {"call": 1, "instructions": 100, "positions": 128}),
        (7, 12, "tao/engine.columns", {"call": 1}),
        (12, 14, "tao/engine.upload", {"call": 1}),
        (14, 15, "tao/engine.upload", {"call": 1}),
        (15, 30, "tao/fused.extract", {"call": 1}),
        (30, 41, "tao/engine.step", {"call": 1}),
        (41, 45, "tao/fused.extract", {"call": 1}),
        (45, 52, "tao/engine.step", {"call": 1}),
        (52, 54, "tao/engine.sync", {"call": 1}),
        (61, 94, "tao/engine.simulate", {"call": 2, "instructions": 50, "positions": 64}),
        (61, 70, "tao/engine.columns", {"call": 2}),
        (70, 80, "tao/engine.step", {"call": 2}),
        (90, 94, "tao/engine.sync", {"call": 2}),
        (96, 105, "tao/engine.simulate", {"call": 3, "instructions": 9, "positions": 64}),
        (96, 99, "tao/engine.columns", {"call": 3}),
    ]))
    return t


def train_trace():
    """One transfer call of three steps in a 100 ms window; busy 20-30,
    50-60, 80-90 ms; the gathers run on the prefetch thread."""
    ops = [(20, 30, "fusion.1"), (50, 60, "fusion.1"), (80, 90, "fusion.1")]
    t = harness.TraceView([ms(ops)], [ms([(20, 30, "jit_step(1)")])],
                          ms([(0, 100, "bench:window"), (0, 100, "bench:train_call")]),
                          0, 100 * MS, {"windows": 48}, config(), PEAK, {"batch_size": 16})
    t.program = sorted(ms([
        (1, 99, "tao/train.run", {"call": 5, "steps": 3, "windows": 48}),
        (1, 10, "tao/train.prepare", {"call": 5}),
        (10, 20, "tao/feed.wait", {"call": 5}),
        (12, 14, "tao/feed.gather", {"call": 5}),
        (20, 21, "tao/train.step", {"call": 5}),
        (21, 50, "tao/feed.wait", {"call": 5}),
        (40, 44, "tao/feed.gather", {"call": 5}),
        (50, 51, "tao/train.step", {"call": 5}),
        (51, 80, "tao/feed.wait", {"call": 5}),
        (70, 73, "tao/feed.gather", {"call": 5}),
        (80, 95, "tao/train.step", {"call": 5}),
        (95, 99, "tao/train.epoch_sync", {"call": 5}),
    ]))
    return t


def test_engine_parts_by_hand():
    t = sim_trace()
    # call 1: columns 7-10 idle; uploads busy; extracts 25-30; steps 30-40
    # and 50-52; sync 52-54.  Call 2: columns 61-70, sync 90-94.  Call 3
    # leaves the window and does not count
    assert read("exposed_columns_ms", t) == pytest.approx((3 + 9) / 2)
    assert read("exposed_upload_ms", t) == pytest.approx(0.0)
    assert read("exposed_extract_ms", t) == pytest.approx(5 / 2)
    assert read("exposed_step_ms", t) == pytest.approx(12 / 2)
    assert read("exposed_sync_ms", t) == pytest.approx((2 + 4) / 2)
    assert read("pad_share_pct", t) == pytest.approx(100 * (1 - 150 / 192))
    for name in ("exposed_step_ms", "pad_share_pct"):
        assert read(name + ".intervals", t) == read(name, t)
    # the five parts against the request spans' exposed host time (24 + 15)
    parts = sum(read(f"exposed_{p}_ms", t) for p in ("columns", "upload", "extract", "step", "sync"))
    assert parts == pytest.approx(((3 + 5 + 12 + 2) + (9 + 4)) / 2)
    assert read("request_exposed_host_ms", t) == pytest.approx((24 + 15) / 2)
    cov = spans.coverage(t, "request")
    assert cov["idle_in_request_ms"] == pytest.approx(39)
    assert cov["idle_in_parts_ms"] == pytest.approx(35)


def test_transfer_parts_by_hand():
    t = train_trace()
    assert read("train_exposed_wait_ms", t) == pytest.approx((10 + 20 + 20) / 3)
    assert read("train_exposed_step_ms", t) == pytest.approx(5 / 3)
    assert read("train_exposed_prepare_ms", t) == pytest.approx(9)
    assert read("train_exposed_sync_ms", t) == pytest.approx(4)
    assert read("train_gather_ms", t) == pytest.approx(3)
    # (wait + step) x steps + prepare + sync against the idle time in the call
    steps = 3
    covered = ((read("train_exposed_wait_ms", t) + read("train_exposed_step_ms", t)) * steps
               + read("train_exposed_prepare_ms", t) + read("train_exposed_sync_ms", t))
    cov = spans.coverage(t, "train_call")
    assert cov["idle_in_train_call_ms"] == pytest.approx(70)
    assert covered == pytest.approx(68) and cov["idle_in_parts_ms"] == pytest.approx(68)


def test_no_program_spans_read_nothing():
    """A program without spans (the parent of the change that added them):
    every new reader returns None and raises nothing."""
    for t in (made_up(), train_trace()):
        t.program = []
        for name in ("exposed_columns_ms", "exposed_upload_ms", "exposed_extract_ms",
                     "exposed_step_ms", "exposed_sync_ms", "pad_share_pct",
                     "train_exposed_wait_ms", "train_exposed_step_ms",
                     "train_exposed_prepare_ms", "train_exposed_sync_ms", "train_gather_ms"):
            assert read(name, t) is None, name


def test_idle_equals_uncovered_sum():
    t = sim_trace()
    for iv in ([(5 * MS, 60 * MS)], [(0, 100 * MS)], [(12 * MS, 13 * MS), (24 * MS, 41 * MS)]):
        assert spans.idle_ns(t, iv) == sum(t.uncovered_ns(s, e) for s, e in iv)


def test_record_round_trip(tmp_path):
    t = sim_trace()
    cell = harness.Cell.load("paper.sim-intervals")
    path = str(tmp_path / "rec.json")
    spans.record(cell, t, path, whole_ms=50)
    with open(path) as f:
        rec = json.load(f)
    # the shortest whole call (call 2, 61-94 ms) and a millisecond each side
    assert (rec["cut"]["t0"], rec["cut"]["t1"]) == (60 * MS, 95 * MS)
    small = spans.from_cut(rec["cut"], rec["work"], t.config, PEAK, rec["traffic"])
    assert read("exposed_columns_ms.intervals", small) == pytest.approx(9)
    assert rec["expect"]["exposed_columns_ms.intervals"] == pytest.approx(9)
    assert rec["expect"]["pad_share_pct.intervals"] == pytest.approx(100 * (1 - 50 / 64))


SIM = [f"exposed_{p}_ms" for p in ("columns", "upload", "extract", "step", "sync")]
HELD = {
    "paper.sim-long": SIM + ["pad_share_pct"],
    "paper.sim-intervals": [m + ".intervals" for m in SIM + ["pad_share_pct"]],
    "paper.transfer-train": ["train_exposed_wait_ms", "train_exposed_step_ms", "train_gather_ms"],
}


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(HERE, "data", "spans_*.json"))))
def test_recorded_spans(path):
    with open(path) as f:
        rec = json.load(f)
    t = spans.from_cut(rec["cut"], rec["work"], config(rec["config"]), PEAK, rec["traffic"])
    assert t.program, "no program spans recorded"
    # the new readers that the cut holds: every one in the simulation
    # cells (a whole call each); the per-step ones in the transfer cell
    # (60 ms from a call's middle: a whole call lasts 3.6 s)
    assert set(HELD[rec["workload"]]) <= set(rec["expect"])
    # every reader of the cell that reads something there, old and new,
    # reads what it read on the chip
    for name, want in rec["expect"].items():
        got = read(name, t)
        assert got == pytest.approx(want, rel=1e-9), name
        if "_roofline" in name or "_pct" in name:
            assert 0.0 <= got <= 100.0
    # the cut's parts against a plain sweep of chip 0's idle time
    for sp in t.program:
        assert spans.idle_ns(t, [sp]) == t.uncovered_ns(sp[0], sp[1])
