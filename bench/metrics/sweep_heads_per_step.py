"""sweep_heads_per_step: the design points each step of the sweep runs
over one batch, averaged over the steps of the ``tao/sweep.group`` spans
that lie in the traced window (each span's ``heads`` weighted by its
``batches``).  1 where each model runs its own step; the sweep's model
count where one stacked step runs them all."""
from bench import spans


def read(t):
    groups = spans.named(t, "sweep.group")
    steps = sum(sp[3]["batches"] for sp in groups)
    if not steps:
        return None
    return sum(sp[3]["heads"] * sp[3]["batches"] for sp in groups) / steps
