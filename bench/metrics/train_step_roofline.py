"""train_step_roofline: the transfer step's least time
(``bench/flops.py``: frozen-embedding forward/backward FLOPs over the peak,
or its weight/optimizer bytes over HBM bandwidth, whichever is larger)
over the device time of its launches (the jitted ``step`` of
``core/transfer.py``, on the "XLA Modules" line)."""

from bench import flops

MODULE = r"^jit_step"


def read(t):
    if t.peak is None:
        return None
    cost = flops.train_step_cost(t.config, t.traffic["batch_size"])
    least = device = 0.0
    for evs in t.matching(MODULE, line="modules"):
        for s, e, _ in evs:
            device += (e - s) / 1e9
            least += flops.least_seconds(cost["flops"], cost["bytes"], t.peak)
    if device <= 0:
        return None
    return 100.0 * least / device
