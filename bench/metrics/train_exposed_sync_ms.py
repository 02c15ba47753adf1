"""train_exposed_sync_ms: chip-0 idle milliseconds inside the
``tao/train.epoch_sync`` spans (the per-epoch ``device_get`` of the step
losses and their sum), per ``tao/train.run`` call that lies in the traced
window."""
from bench import spans


def read(t):
    return spans.exposed_ms(t, "train.epoch_sync", per="train.run")
