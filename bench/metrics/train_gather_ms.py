"""train_gather_ms: the mean duration in milliseconds of a
``tao/feed.gather`` span in the traced window: host time to gather one
training batch, in whichever thread gathers it (the prefetch producer on
an accelerator).  Host time, not device idle time."""
from bench import spans


def read(t):
    gathers = spans.named(t, "feed.gather")
    if not gathers:
        return None
    return sum(e - s for s, e, *_ in gathers) / len(gathers) / 1e6
