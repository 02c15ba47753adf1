"""simnet_scan_roofline: the SimNet scan's least time over its device time.
Each launch is counted by its own rows and iterations, the arguments of its
``tao/simnet.scan`` span (``bench/flops_simnet.py``: the larger of its
FLOPs over the peak and its bytes over HBM bandwidth); the device time is
that of the ``jit_simnet_scan`` executable on the "XLA Modules" line, both
over the traced window."""

from bench import flops, flops_simnet, spans

MODULE = r"^jit_simnet_scan"


def read(t):
    if t.peak is None:
        return None
    least = 0.0
    for sp in spans.named(t, "simnet.scan"):
        cost = flops_simnet.scan_cost(t.config, int(sp[3]["rows"]), int(sp[3]["iterations"]))
        least += flops.least_seconds(cost["flops"], cost["bytes"], t.peak)
    device = sum((e - s) / 1e9 for evs in t.matching(MODULE, line="modules") for s, e, _ in evs)
    if device <= 0 or least <= 0:
        return None
    return 100.0 * least / device

