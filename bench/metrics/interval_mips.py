"""interval_mips: ``sim_mips`` of the sampled-interval cell: simulated
instructions of every request completed in the window, in millions, over
the window's seconds (host clock)."""
from bench.harness import load_module

value = load_module("metrics", "sim_mips").value
