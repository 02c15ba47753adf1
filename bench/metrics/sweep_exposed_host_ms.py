"""sweep_exposed_host_ms: the mean, over the ``TraceSweeper.run`` calls
that lie in the traced window, of the chip-0 idle milliseconds inside the
call's ``tao/sweep.call`` span: the host work of a sweep call (grouping,
columns, extraction and step dispatch, the re-layout onto the mesh, the
syncs) that the device waits for.  Chip 0 runs every extraction program,
so its idle time is the host's."""
from bench import spans


def read(t):
    return spans.exposed_ms(t, "sweep.call", per="sweep.call")
