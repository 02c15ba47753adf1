"""train_exposed_step_ms: chip-0 idle milliseconds inside the
``tao/train.step`` spans (the host's dispatch of one jitted transfer
step), per ``tao/train.step`` span; both lie in the traced window."""
from bench import spans


def read(t):
    return spans.exposed_ms(t, "train.step", per="train.step")
