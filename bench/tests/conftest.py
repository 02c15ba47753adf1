"""The benchmark's own tests, on the CPU at tiny sizes:

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q

(four virtual devices are set here for the sharded cell)."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
