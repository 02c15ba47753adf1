"""sim_step_roofline: the engine step's least time (``bench/flops.py``:
the larger of its forward FLOPs over the peak and its bytes -- weights,
inputs -- over HBM bandwidth) over the device time of its launches (the
jitted ``body`` of ``engine/runner.py``, on the "XLA Modules" line).  On a
sharded plan each chip runs ``batch_size / chips`` rows with the whole
weights."""

from bench import flops

MODULE = r"^jit_body|^jit_shmap_body|body\("


def read(t):
    if t.peak is None:
        return None
    w = t.config
    chips = len(t.devices)
    cost = flops.step_cost({**w, "batch_size": w["batch_size"] // chips})
    least = device = 0.0
    for evs in t.matching(MODULE, line="modules"):
        for s, e, _ in evs:
            device += (e - s) / 1e9
            least += flops.least_seconds(cost["flops"], cost["bytes"], t.peak)
    if device <= 0:
        return None
    return 100.0 * least / device
