"""exposed_upload_ms.intervals: ``exposed_upload_ms``, read in the sampled-
interval cell, where it moves ``interval_mips``."""
from bench.harness import load_module

read = load_module("metrics", "exposed_upload_ms").read
