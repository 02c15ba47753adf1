"""mfu_pct.simnet: the forward FLOPs of the instructions counted in the
traced window (``bench/flops_simnet.py``, ~53.8 MFLOP per prediction at the
configuration's widths; warm-up predictions and padding rows are not
counted), over the traced seconds times the chips times the chip's peak
FLOP/s."""

from bench import flops_simnet


def read(t):
    n = t.work.get("instructions", 0)
    if not n or t.peak is None or t.seconds <= 0:
        return None
    done = n * flops_simnet.forward_flops_per_prediction(t.config)
    return 100.0 * done / (t.seconds * len(t.devices) * t.peak["flops_per_s"])
