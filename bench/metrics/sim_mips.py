"""sim_mips: simulated instructions of every request completed in the
window (the engine's ``num_instructions``), in millions, over the window's
seconds (host clock)."""


def value(win) -> float:
    return win.total("instructions") / 1e6 / win.seconds
