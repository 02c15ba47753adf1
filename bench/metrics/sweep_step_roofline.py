"""sweep_step_roofline: the stacked sweep step's least time over the
device time of its launches (the jitted ``body`` of ``engine/runner.py``
on the "XLA Modules" line, each chip's own) that start inside a
``tao/sweep.group`` span.  A launch of a group of K heads runs K models
over ``batch_size / chips`` rows: K times the one-model FLOPs and weights
(``bench/flops.py`` ``step_cost``, ``param_bytes``), the inputs once; its
least time is the larger of the FLOPs over the peak and the bytes over
HBM bandwidth.  A program without the sweep's spans reads nothing."""
import bisect

from bench import flops, spans

MODULE = r"^jit_body|^jit_shmap_body|body\("


def read(t):
    groups = spans.named(t, "sweep.group")
    if t.peak is None or not groups:
        return None
    w = t.config
    one = flops.step_cost({**w, "batch_size": w["batch_size"] // len(t.devices)})
    weights = flops.param_bytes(w)
    starts = [sp[0] for sp in groups]
    least = device = 0.0
    for evs in t.matching(MODULE, line="modules"):
        for s, e, _ in evs:
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or not s < groups[i][1]:
                continue
            k = groups[i][3]["heads"]
            device += (e - s) / 1e9
            least += flops.least_seconds(k * one["flops"], one["bytes"] + (k - 1) * weights, t.peak)
    if device <= 0:
        return None
    return 100.0 * least / device
