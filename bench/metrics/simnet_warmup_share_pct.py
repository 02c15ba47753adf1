"""simnet_warmup_share_pct: the share of SimNet's predictions spent on
warm-up (each sub-trace after the first runs the ROB's depth of
instructions before its counted ones, which are predicted again there),
over the ``tao/simnet.simulate`` calls in the traced window: 100 x the
calls' ``warmup`` over their ``warmup`` plus ``instructions``."""
from bench import spans


def read(t):
    calls = spans.named(t, "simnet.simulate")
    warm = sum(int(sp[3]["warmup"]) for sp in calls)
    total = warm + sum(int(sp[3]["instructions"]) for sp in calls)
    if not total:
        return None
    return 100.0 * warm / total
