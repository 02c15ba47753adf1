"""cross_chip_pct.sweep: the share of device-busy time spent in collectives
between devices (all-reduce, all-gather, reduce-scatter, collective-permute,
all-to-all, send/recv, each with its -start/-done halves), averaged over the
chips (profiler trace, "XLA Ops" line).  An op counts by its own name
(``harness.op_name``: ``all-reduce.1``), not by its whole HLO text, whose
operand list names every op whose result it reads.  ``copy-start`` /
``copy-done`` do not count: on the TPU they move a buffer between memory
spaces of one chip (the step's weights prefetched into an ``S(1)`` layout),
not between chips.  The re-layout of a batch onto the mesh
(``plan.device_put``) is a runtime transfer, not an op on this line."""
import re

from bench.harness import op_name

PATTERN = re.compile(
    r"(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|send|recv)([.-]|$)"
)


def read(t):
    if len(t.devices) < 2:
        return None
    busy = sum(t.busy_ns(c) for c in range(len(t.devices)))
    if busy <= 0:
        return None
    coll = sum(
        sum(e - s for s, e in t.union([ev for ev in evs if PATTERN.match(op_name(ev[2]))]))
        for evs in t.devices
    )
    return 100.0 * coll / busy
