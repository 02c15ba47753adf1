"""exposed_step_ms: the mean, over the ``StreamingEngine.simulate`` calls
that lie in the traced window, of the chip-0 idle milliseconds inside
the call's ``tao/engine.step`` spans (one per batch: the dispatch of the
jitted step): the part of ``request_exposed_host_ms`` that this host
work leaves the device waiting."""
from bench import spans


def read(t):
    return spans.exposed_ms(t, "engine.step", per="engine.simulate")
