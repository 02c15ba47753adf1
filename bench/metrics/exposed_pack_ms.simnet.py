"""exposed_pack_ms.simnet: the mean, over the ``SimNetEngine.simulate``
calls that lie in the traced window, of the chip-0 idle milliseconds inside
the call's ``tao/simnet.pack`` span (packing the request's rows into 8 B
words and reading the detailed trace's MPKI on the host): the part of
``request_exposed_host_ms`` that this host work leaves the device
waiting."""
from bench import spans


def read(t):
    return spans.exposed_ms(t, "simnet.pack", per="simnet.simulate")
