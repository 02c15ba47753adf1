"""request_exposed_host_ms: the mean, over the requests in the traced
window, of the part of the benchmark's span around each
``StreamingEngine.simulate`` call in which no operation runs on the chip:
host work (column prep, upload, dispatch, the final sync) that the device
waits for."""


def read(t):
    spans = t.spans_named("request")
    if not spans or not t.devices:
        return None
    return sum(t.uncovered_ns(s, e) for s, e, _ in spans) / len(spans) / 1e6
