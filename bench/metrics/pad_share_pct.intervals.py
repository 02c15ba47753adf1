"""pad_share_pct.intervals: ``pad_share_pct``, read in the sampled-interval
cell, where it moves ``interval_mips``."""
from bench.harness import load_module

read = load_module("metrics", "pad_share_pct").read
