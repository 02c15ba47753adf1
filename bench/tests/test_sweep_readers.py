"""The readers of the four-chip sweep cell (``dse32.sweep-4chip``), by hand
on a made-up trace that holds the program's ``tao/sweep.*`` spans, and on
the same trace without them (a program that has no such spans)."""
import pytest

from bench import flops, harness
from bench.tests.test_flops import config
from bench.tests.test_reduce import PEAK

MS = 1_000_000
CELL = "dse32.sweep-4chip"
NEW = ["device_idle_pct.sweep", "mfu_pct.sweep", "sweep_step_roofline", "cross_chip_pct.sweep",
       "sweep_exposed_host_ms", "sweep_heads_per_step"]
# the engine's readers, which read each trace group's simulate in the sweep too
ENGINE = ["exposed_columns_ms", "exposed_upload_ms", "exposed_extract_ms", "exposed_step_ms",
          "exposed_sync_ms", "pad_share_pct", "fused_kernel_roofline"]
# a live trace names an op by its HLO text, operands included
ALL_REDUCE = "%all-reduce.3 = f32[32]{0} all-reduce(%fusion.1), replica_groups={{0,1,2,3}}"
READS_IT = "%fusion.1 = f32[32,16]{1,0} fusion(%all-reduce.3, %param.2), kind=kLoop"
# a move between memory spaces of one chip (the step's weight prefetch)
PREFETCH = "%copy-done.5 = f32[32,512]{1,0:T(8,128)S(1)} copy-done((f32[32,512]{1,0}), %copy-start.5)"


def ms(evs):
    return [(s * MS, e * MS, *rest) for s, e, *rest in evs]


def read(name, t):
    return harness.load_module("metrics", name).read(t)


def sweep_trace(program=True):
    """A 100 ms window on four chips.  Every chip runs a step launch of
    20 ms (10-30) and one of 10 ms (40-50), each ending in a 2 ms
    all-reduce, and a launch outside the sweep (90-92); the first launch
    holds an on-chip prefetch copy (27-28), and the second starts with an
    op that reads the first one's all-reduce (40-48).  Chip
    0 also runs two extraction programs (5-8, 32-35).  One sweep call
    (4-60) of two trace groups: 32 heads (4-31), then 16 heads (31-58),
    each one simulate whose one batch is extracted in (4-9), (31-36)."""
    devices, modules = [], []
    for chip in range(4):
        ops = [(10, 27, "fusion.1"), (27, 28, PREFETCH), (28, 30, ALL_REDUCE), (40, 48, READS_IT),
               (48, 50, ALL_REDUCE), (90, 92, "fusion.9")]
        mods = [(10, 30, "jit_body(1)"), (40, 50, "jit_body(1)"), (90, 92, "jit_body(1)")]
        if chip == 0:
            ops += [(5, 8, "custom-call.2"), (32, 35, "custom-call.2")]
            mods += [(5, 8, "jit__fused_padded(2)"), (32, 35, "jit__fused_padded(2)")]
        devices.append(sorted(ms(ops)))
        modules.append(sorted(ms(mods)))
    spans = ms([(0, 100, "bench:window"), (3, 61, "bench:sweep_call")])
    t = harness.TraceView(devices, modules, spans, 0, 100 * MS, {"instructions": 8_388_608},
                          config("tao-paper-dse32"), PEAK, {"feature_backend": "fused"})
    t.program = sorted(ms([
        (4, 60, "tao/sweep.call", {"call": 7, "jobs": 64, "traces": 2, "heads": 32}),
        (4, 31, "tao/sweep.group", {"call": 7, "trace": 0, "heads": 32, "batches": 1}),
        (4, 31, "tao/engine.simulate", {"call": 8, "instructions": 2064, "positions": 8256,
                                        "batches": 1, "heads": 32}),
        (31, 58, "tao/sweep.group", {"call": 7, "trace": 1, "heads": 16, "batches": 1}),
        (31, 58, "tao/engine.simulate", {"call": 9, "instructions": 2064, "positions": 8256,
                                         "batches": 1, "heads": 16}),
        (4, 9, "tao/fused.extract", {"call": 8}),
        (31, 36, "tao/fused.extract", {"call": 9}),
    ])) if program else []
    return t


def test_the_cell_reads_its_metrics():
    cell = harness.Cell.load(CELL)
    assert set(NEW + ENGINE) <= {m["name"] for m in cell.per_layer}
    assert [m["name"] for m in cell.end_to_end] == ["sim_mips", "setup_s"]
    assert cell.chips == 4


def test_sweep_readers_by_hand():
    t = sweep_trace()
    w = t.config
    # busy: chip 0 3 + 20 + 3 + 10 + 2 = 38 ms, chips 1-3 20 + 10 + 2 = 32 ms
    assert read("device_idle_pct.sweep", t) == pytest.approx(100 * (1 - (38 + 3 * 32) / 4 / 100))
    # chip 0 inside the call (4-60): 56 ms less 3 + 20 + 3 + 10 busy
    assert read("sweep_exposed_host_ms", t) == pytest.approx(20.0)
    assert read("sweep_heads_per_step", t) == pytest.approx((32 + 16) / 2)
    # 4 ms of all-reduce per chip over all busy time; neither the op that
    # only reads the all-reduce's result nor the on-chip copy counts
    assert read("cross_chip_pct.sweep", t) == pytest.approx(100 * 16 / (38 + 3 * 32))
    mfu = 100 * 8_388_608 * flops.forward_flops_per_instruction(w) / (0.1 * 4 * PEAK["flops_per_s"])
    assert read("mfu_pct.sweep", t) == pytest.approx(mfu)
    # the two launches inside groups, per chip: K models over 16 rows each,
    # K weights and the inputs once; the launch at 90 ms is outside any group
    one = flops.step_cost({**w, "batch_size": 16})
    weights = flops.param_bytes(w)

    def least(k):
        return flops.least_seconds(k * one["flops"], one["bytes"] + (k - 1) * weights, PEAK)

    got = read("sweep_step_roofline", t)
    assert got == pytest.approx(100 * (least(32) + least(16)) / 0.030)
    assert 0 < got < 100
    # one head: the one-model step's cost
    assert least(1) == flops.least_seconds(one["flops"], one["bytes"], PEAK)


def test_engine_readers_read_each_group_simulate():
    t = sweep_trace()
    w = t.config
    # chip 0 idles 4-5 and 8-9 in the first extraction, 31-32 and 35-36 in
    # the second: 2 ms per simulate
    assert read("exposed_extract_ms", t) == pytest.approx(2.0)
    assert read("pad_share_pct", t) == pytest.approx(100 * (1 - 2 * 2064 / (2 * 8256)))
    least = flops.least_seconds(
        0.0, flops.kernel_bytes_per_call(w, w["batch_size"] * w["window"]), PEAK)
    assert read("fused_kernel_roofline", t) == pytest.approx(100 * 2 * least / 0.006)


def test_without_sweep_spans_the_span_readers_read_nothing():
    """The parent program has no ``tao/sweep.*`` spans: the readers that
    need them return None and raise nothing; the device readers read."""
    t = sweep_trace(program=False)
    for name in ("sweep_step_roofline", "sweep_exposed_host_ms", "sweep_heads_per_step"):
        assert read(name, t) is None, name
    for name in ("exposed_extract_ms", "pad_share_pct"):
        assert read(name, t) is None, name
    for name in ("device_idle_pct.sweep", "mfu_pct.sweep", "cross_chip_pct.sweep",
                 "fused_kernel_roofline"):
        assert read(name, t) is not None, name


def test_spans_outside_the_window_do_not_count():
    t = sweep_trace()
    t.t1 = 50 * MS  # the window closes inside the second group and the call
    assert read("sweep_heads_per_step", t) == pytest.approx(32.0)
    assert read("sweep_exposed_host_ms", t) is None
