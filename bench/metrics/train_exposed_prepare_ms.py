"""train_exposed_prepare_ms: chip-0 idle milliseconds inside the
``tao/train.prepare`` spans (step lookup, optimizer state, placement
before the first step), per ``tao/train.run`` call that lies in the
traced window."""
from bench import spans


def read(t):
    return spans.exposed_ms(t, "train.prepare", per="train.run")
