"""Run one benchmark cell once, on the chip this machine holds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints counters on earlier lines and, last, one JSON line: ``correct``,
``attempted``, ``failed``, ``metrics`` (``--trace 0``: the cell's end-to-end
metrics; ``--trace 1``: its per-layer metrics), ``device`` and, traced,
``breakdown``; then ``checks``, each number compared with its limit (also the
last lines of standard error).  Without a TPU holding the cell's chips it
exits 2 and prints no result line.

    JAX_PLATFORMS=cpu python bench/run.py --workload <cell> --rehearse
        tiny widths on the CPU, Pallas in interpret mode; exits 3, no result
        line (add XLA_FLAGS=--xla_force_host_platform_device_count=4 for a
        four-chip cell)

    python bench/run.py --workload <cell> --seed <n> --calibrate <k>
        the readings that set the cell's limits: the program on k seeds from
        <n> and the control (the reference in the next lower precision) on
        the first three, one short window each; prints them, no result line
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))
# the compile cache lives in the checkout unless the machine names one
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".cache", "jax"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--calibrate", type=int, default=0, metavar="K")
    ap.add_argument("--record-trace", default=None, metavar="PATH",
                    help="with --trace 1: write a small recorded trace for bench/tests")
    args = ap.parse_args(argv)

    from bench import harness

    if args.calibrate:
        from bench import calibrate

        return calibrate.main(args)
    result = harness.run_cell(args, T_START, require_chip=not args.rehearse)
    if result is None:
        return 2
    if args.rehearse:
        print(f"# rehearsal: {json.dumps(result)}")
        print("rehearsal passed; no result line off the chip" if result["correct"]
              else "rehearsal: correct is false", flush=True)
        return 3 if result["correct"] else 1
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
