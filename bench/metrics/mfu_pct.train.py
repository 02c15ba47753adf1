"""mfu_pct.train: the transfer step's FLOPs per window (``bench/flops.py``:
frozen-embedding forward, adapt and prediction network forward and
backward, ~15.5 GFLOP per window) times the windows trained in the traced
window, over the traced seconds times the chips times the peak FLOP/s."""

from bench import flops


def read(t):
    n = t.work.get("windows", 0)
    if not n or t.peak is None or t.seconds <= 0:
        return None
    done = n * flops.train_flops_per_window(t.config)
    return 100.0 * done / (t.seconds * len(t.devices) * t.peak["flops_per_s"])
