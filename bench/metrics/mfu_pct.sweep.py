"""mfu_pct.sweep: the forward FLOPs of the design-point instructions the
sweep calls in the traced window completed (``bench/flops.py``, ~40.6
MFLOP per instruction and design point at the published widths; padding
positions are not counted), over the traced seconds times the chips times
the chip's peak FLOP/s."""

from bench import flops


def read(t):
    n = t.work.get("instructions", 0)
    if not n or t.peak is None or t.seconds <= 0:
        return None
    done = n * flops.forward_flops_per_instruction(t.config)
    return 100.0 * done / (t.seconds * len(t.devices) * t.peak["flops_per_s"])
