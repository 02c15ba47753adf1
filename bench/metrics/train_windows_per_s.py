"""train_windows_per_s: training windows of every step completed in the
window, over the window's seconds (host clock)."""


def value(win) -> float:
    return win.total("windows") / win.seconds
