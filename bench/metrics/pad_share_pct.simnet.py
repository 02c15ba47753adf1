"""pad_share_pct.simnet: the share of the SimNet scan's predictions that
predict no instruction, over the ``SimNetEngine.simulate`` calls that lie in
the traced window: 100 x (1 - the calls' ``instructions`` plus ``warmup``
over their ``positions``, each launch's rows x its iterations), arguments
of the ``tao/simnet.simulate`` span.  Padding is the plan's rows beyond the
request's sub-traces, the first sub-trace's masked warm-up and the last
one's missing tail: device work that ``sim_mips`` does not count."""
from bench import spans


def read(t):
    calls = spans.named(t, "simnet.simulate")
    positions = sum(int(sp[3]["positions"]) for sp in calls)
    if not positions:
        return None
    done = sum(int(sp[3]["instructions"]) + int(sp[3]["warmup"]) for sp in calls)
    return 100.0 * (1.0 - done / positions)
