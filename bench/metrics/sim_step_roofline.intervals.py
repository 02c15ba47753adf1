"""sim_step_roofline.intervals: ``sim_step_roofline``, read in the sampled-
interval cell, where it moves ``interval_mips``."""
from bench.harness import load_module

read = load_module("metrics", "sim_step_roofline").read
