"""device_idle_pct.train: ``device_idle_pct.sim``, read in the training
cell, where it moves ``train_windows_per_s``."""
from bench.harness import load_module

read = load_module("metrics", "device_idle_pct.sim").read
