"""train_exposed_wait_ms: chip-0 idle milliseconds inside the
``tao/feed.wait`` spans (the training loop blocked on its next prefetched
batch), per ``tao/train.step`` span; both lie in the traced window."""
from bench import spans


def read(t):
    return spans.exposed_ms(t, "feed.wait", per="train.step")
