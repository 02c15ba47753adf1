"""request_exposed_host_ms.intervals: ``request_exposed_host_ms``, read in
the sampled-interval cell, where it moves ``interval_mips``."""
from bench.harness import load_module

read = load_module("metrics", "request_exposed_host_ms").read
