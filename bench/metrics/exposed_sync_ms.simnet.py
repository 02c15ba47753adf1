"""exposed_sync_ms.simnet: the mean, over the ``SimNetEngine.simulate``
calls that lie in the traced window, of the chip-0 idle milliseconds inside
the call's ``tao/simnet.sync`` span (the one pull of every launch's cycles
and context sums, and the result): the part of ``request_exposed_host_ms``
that this host work leaves the device waiting."""
from bench import spans


def read(t):
    return spans.exposed_ms(t, "simnet.sync", per="simnet.simulate")
