"""device_idle_pct.intervals: ``device_idle_pct.sim``, read in the sampled-
interval cell, where it moves ``interval_mips``."""
from bench.harness import load_module

read = load_module("metrics", "device_idle_pct.sim").read
