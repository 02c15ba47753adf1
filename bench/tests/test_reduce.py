"""The trace reducers: by hand on a made-up trace, and on a small trace
recorded on the chip (``data/trace_*.json``: the first 80 ms of a traced
run's window, as ``TraceView.cut`` writes it)."""
import glob
import json
import os

import pytest

from bench import flops, harness
from bench.tests.test_flops import config

HERE = os.path.dirname(os.path.abspath(__file__))
PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
MS = 1_000_000


def made_up():
    # window 0..100 ms; one chip; two requests; ops in ms
    ops = [(10, 20, "fusion.1"), (15, 25, "fusion.2"), (40, 50, "custom-call.3"), (70, 90, "fusion.1")]
    mods = [(10, 25, "jit_body(123)"), (40, 50, "jit__fused_padded(7)"), (70, 90, "jit_body(123)")]
    spans = [(0, 100, "bench:window"), (6, 55, "bench:request"), (60, 95, "bench:request")]
    ms = lambda evs: [(s * MS, e * MS, n) for s, e, n in evs]  # noqa: E731
    w = config()
    return harness.TraceView([ms(ops)], [ms(mods)], ms(spans), 0, 100 * MS,
                             {"instructions": 8256 * 3}, w, PEAK, {"batch_size": 16})


def test_busy_idle_and_exposed_host():
    t = made_up()
    assert t.union(t.devices[0]) == [(10 * MS, 25 * MS), (40 * MS, 50 * MS), (70 * MS, 90 * MS)]
    assert t.busy_s() == pytest.approx(0.045)
    assert harness.load_module("metrics", "device_idle_pct.sim").read(t) == pytest.approx(55.0)
    # request 1: 6..55 with 25 ms busy; request 2: 60..95 with 20 ms busy
    exposed = harness.load_module("metrics", "request_exposed_host_ms").read(t)
    assert exposed == pytest.approx(((49 - 25) + (35 - 20)) / 2)


def test_rooflines_and_mfu():
    t = made_up()
    w = t.config
    step = flops.step_cost(w)
    want = 100 * 2 * flops.least_seconds(step["flops"], step["bytes"], PEAK) / 0.035
    assert harness.load_module("metrics", "sim_step_roofline").read(t) == pytest.approx(want)
    kb = flops.kernel_bytes_per_call(w, w["batch_size"] * w["window"])
    want = 100 * (kb / PEAK["hbm_bytes_per_s"]) / 0.010
    assert harness.load_module("metrics", "fused_kernel_roofline").read(t) == pytest.approx(want)
    mfu = 100 * 8256 * 3 * flops.forward_flops_per_instruction(w) / (0.1 * PEAK["flops_per_s"])
    assert harness.load_module("metrics", "mfu_pct.sim").read(t) == pytest.approx(mfu)
    assert harness.load_module("metrics", "cross_chip_pct").read(t) is None  # one chip


def test_breakdown_names_gaps_by_span():
    b = harness.breakdown(made_up())
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(0.030)]
    gaps = {(name, round(s, 6)) for name, s in b["idle_gaps"]}
    # each gap is named by the span open at its middle
    assert gaps == {("between_requests", 0.01),  # 0..10, before request 1
                    ("request", 0.015),          # 25..40, inside request 1
                    ("request", 0.02),           # 50..70, request 2 opens at 60
                    ("between_requests", 0.01)}  # 90..100, after request 2


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(HERE, "data", "trace_*.json"))))
def test_recorded_trace(path):
    with open(path) as f:
        rec = json.load(f)
    w = config(rec["config"])
    t = harness.TraceView.from_cut(rec["cut"], rec["work"], w, PEAK, rec["traffic"])
    # every reader that finds its events returns a share within [0, 100]
    for name, want in rec["expect"].items():
        got = harness.load_module("metrics", name).read(t)
        assert got == pytest.approx(want, rel=1e-9), name
        if "_roofline" in name or "_pct" in name:
            assert 0.0 <= got <= 100.0
    # busy time recomputed by a plain sweep over the chip's ops
    ops = sorted(rec["cut"]["devices"][0])
    busy, end = 0, None
    for s, e, _ in ops:
        s = max(s, end) if end is not None else s
        if e > s:
            busy += e - s
        end = e if end is None else max(end, e)
    assert t.busy_ns(0) == busy
