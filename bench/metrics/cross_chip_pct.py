"""cross_chip_pct: the share of device-busy time spent in collectives and
copies between devices (all-reduce, all-gather, collective-permute,
send/recv, async copies), averaged over the chips (profiler trace, "XLA Ops"
line)."""

PATTERN = r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|copy-start|copy-done|send|recv"


def read(t):
    if len(t.devices) < 2:
        return None
    busy = sum(t.busy_ns(c) for c in range(len(t.devices)))
    if busy <= 0:
        return None
    coll = sum(sum(e - s for s, e in t.union(evs)) for evs in t.matching(PATTERN))
    return 100.0 * coll / busy
