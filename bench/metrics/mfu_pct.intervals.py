"""mfu_pct.intervals: ``mfu_pct.sim``, read in the sampled-interval cell,
where it moves ``interval_mips``."""
from bench.harness import load_module

read = load_module("metrics", "mfu_pct.sim").read
