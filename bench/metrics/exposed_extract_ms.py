"""exposed_extract_ms: the mean, over the ``StreamingEngine.simulate``
calls that lie in the traced window, of the chip-0 idle milliseconds
inside the call's ``tao/fused.extract`` spans (one per batch: the column
slices, the ``_fused_padded`` launch, the eager signed-log, the
reshapes): the part of ``request_exposed_host_ms`` that this host work
leaves the device waiting."""
from bench import spans


def read(t):
    return spans.exposed_ms(t, "fused.extract", per="engine.simulate")
