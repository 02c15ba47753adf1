"""fused_kernel_roofline.intervals: ``fused_kernel_roofline``, read in the
sampled-interval cell, where it moves ``interval_mips``."""
from bench.harness import load_module

read = load_module("metrics", "fused_kernel_roofline").read
