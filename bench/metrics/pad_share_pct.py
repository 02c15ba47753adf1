"""pad_share_pct: the share of the engine step's positions that are
padding, over the ``StreamingEngine.simulate`` calls that lie in the
traced window: 100 x (1 - the calls' ``instructions`` over their
``positions``, batches x batch size x window), both arguments of the
``tao/engine.simulate`` span.  Padding is device work that the MIPS
metrics do not count."""
from bench import spans


def read(t):
    calls = spans.named(t, "engine.simulate")
    positions = sum(sp[3]["positions"] for sp in calls)
    if not positions:
        return None
    return 100.0 * (1.0 - sum(sp[3]["instructions"] for sp in calls) / positions)
