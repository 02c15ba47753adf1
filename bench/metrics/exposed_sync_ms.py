"""exposed_sync_ms: the mean, over the ``StreamingEngine.simulate`` calls
that lie in the traced window, of the chip-0 idle milliseconds inside
the call's ``tao/engine.sync`` spans (the final ``device_get`` of the
carry, each metric's finalize, the result): the part of
``request_exposed_host_ms`` that this host work leaves the device
waiting."""
from bench import spans


def read(t):
    return spans.exposed_ms(t, "engine.sync", per="engine.simulate")
