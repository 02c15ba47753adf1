"""The readers of the SimNet cell (``simnet-c3.sim-long``), by hand on a
made-up trace that holds the program's ``tao/simnet.*`` spans, and on the
same trace without them (a program that has no such spans); and the
FLOP count against the C3 widths."""
import pytest

from bench import flops, flops_simnet, harness
from bench.tests.test_flops import config
from bench.tests.test_reduce import PEAK

MS = 1_000_000
CELL = "simnet-c3.sim-long"
# the cell's own readers, and the accepted ones it is listed under
NEW = ["mfu_pct.simnet", "simnet_scan_roofline", "simnet_warmup_share_pct",
       "pad_share_pct.simnet", "exposed_pack_ms.simnet", "exposed_sync_ms.simnet",
       "device_idle_pct.sim", "request_exposed_host_ms"]


def ms(evs):
    return [(s * MS, e * MS, *rest) for s, e, *rest in evs]


def read(name, t):
    return harness.load_module("metrics", name).read(t)


def simnet_trace(program=True):
    """A 100 ms window on one chip: one request (2-90) of 4,096
    instructions in two launches, one of 4 rows (10-40) and one of 2 rows
    (42-80), and a scan launch outside any request (92-95)."""
    ops = [(10, 40, "fusion.1"), (42, 80, "fusion.1"), (92, 95, "fusion.1")]
    mods = [(10, 40, "jit_simnet_scan(3)"), (42, 80, "jit_simnet_scan(4)"),
            (92, 95, "jit_body(1)")]
    spans = ms([(0, 100, "bench:window"), (2, 90, "bench:request")])
    t = harness.TraceView([sorted(ms(ops))], [sorted(ms(mods))], spans, 0, 100 * MS,
                          {"instructions": 4096}, config("simnet-c3"), PEAK, {})
    t.program = sorted(ms([
        (2, 89, "tao/simnet.simulate", {"call": 5, "instructions": 4096, "subtraces": 4,
                                        "iterations": 2304, "positions": 6 * 1152,
                                        "padded_rows": 2, "warmup": 384}),
        (3, 9, "tao/simnet.pack", {"call": 5}),
        (9, 10, "tao/simnet.scan", {"call": 5, "rows": 4, "iterations": 1152}),
        (10, 11, "tao/simnet.scan", {"call": 5, "rows": 2, "iterations": 1152}),
        (80, 89, "tao/simnet.sync", {"call": 5}),
    ])) if program else []
    return t


def test_the_cell_reads_its_metrics():
    cell = harness.Cell.load(CELL)
    assert set(NEW) <= {m["name"] for m in cell.per_layer}
    assert "sim_mips" in {m["name"] for m in cell.end_to_end}


def test_flops_per_prediction_at_the_c3_widths():
    w = config("simnet-c3")
    # 7.27 + 2 x 21.14 + 4.23 MFLOP (convolutions, dense) and the head
    assert flops_simnet.forward_flops_per_prediction(w) == (
        2 * 129 * 5 * 44 * 128 + 2 * (2 * 129 * 5 * 128 * 128) + 2 * 129 * 128 * 128 + 2 * 128 * 2)


def test_readers_by_hand():
    t = simnet_trace()
    w = t.config
    # busy 10-40, 42-80, 92-95: 71 of 100 ms
    assert read("device_idle_pct.sim", t) == pytest.approx(29.0)
    assert read("mfu_pct.simnet", t) == pytest.approx(
        100 * 4096 * flops_simnet.forward_flops_per_prediction(w) / (0.1 * 197e12))
    least = sum(flops.least_seconds(c["flops"], c["bytes"], PEAK)
                for c in (flops_simnet.scan_cost(w, 4, 1152), flops_simnet.scan_cost(w, 2, 1152)))
    assert read("simnet_scan_roofline", t) == pytest.approx(100 * least / 0.068)
    assert read("simnet_warmup_share_pct", t) == pytest.approx(100 * 384 / (384 + 4096))
    # 6 rows x 1,152 iterations, of which 4,096 counted and 384 warm-up
    assert read("pad_share_pct.simnet", t) == pytest.approx(100 * (1 - (4096 + 384) / 6912))
    # the request 2-90 is idle in 2-10 and 40-42 and 80-90; of that, 3-9 in
    # the pack span and 80-89 in the sync span
    assert read("request_exposed_host_ms", t) == pytest.approx(20.0)
    assert read("exposed_pack_ms.simnet", t) == pytest.approx(6.0)
    assert read("exposed_sync_ms.simnet", t) == pytest.approx(9.0)


def test_readers_without_program_spans():
    t = simnet_trace(program=False)
    assert read("simnet_scan_roofline", t) is None
    assert read("simnet_warmup_share_pct", t) is None
    assert read("pad_share_pct.simnet", t) is None
    assert read("exposed_pack_ms.simnet", t) is None
    assert read("exposed_sync_ms.simnet", t) is None
    assert read("device_idle_pct.sim", t) == pytest.approx(29.0)
