"""exposed_upload_ms: the mean, over the ``StreamingEngine.simulate`` calls
that lie in the traced window, of the chip-0 idle milliseconds inside
the call's ``tao/engine.upload`` spans (the request's host-to-device
placement: column upload and pad, the validity mask, the initial carry):
the part of ``request_exposed_host_ms`` that this host work leaves the
device waiting."""
from bench import spans


def read(t):
    return spans.exposed_ms(t, "engine.upload", per="engine.simulate")
