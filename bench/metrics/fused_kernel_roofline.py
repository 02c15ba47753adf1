"""fused_kernel_roofline: the fused extraction megakernel's least time
(``bench/flops.py``: its bytes over the chip's HBM bandwidth; its integer
work is not bound by the FLOP peak) over its device time, per launch of
the ``_fused_padded`` executable (one launch per engine batch of
``batch_size * window`` positions), summed over the traced window and the
chips."""

from bench import flops

# the executable that wraps the Mosaic kernel, on the "XLA Modules" line
MODULE = r"_fused_padded"


def read(t):
    if t.peak is None:
        return None
    w = t.config
    least = device = 0.0
    for evs in t.matching(MODULE, line="modules"):
        for s, e, _ in evs:
            device += (e - s) / 1e9
            least += flops.least_seconds(
                0.0, flops.kernel_bytes_per_call(w, w["batch_size"] * w["window"]), t.peak)
    if device <= 0:
        return None
    return 100.0 * least / device
