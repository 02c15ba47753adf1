"""exposed_extract_ms.intervals: ``exposed_extract_ms``, read in the
sampled-interval cell, where it moves ``interval_mips``."""
from bench.harness import load_module

read = load_module("metrics", "exposed_extract_ms").read
