"""The SimNet cell's ``latency_gap`` on the CPU, with the chip's matmul
precision emulated: a second witness beside ``run.py --calibrate``.

On the TPU the program's float32 matmuls at default precision run as one
bfloat16 pass with float32 sums.  This script simulates a slice of one
program's ``UARCH_C`` detailed rows with the engine (float32 on the CPU),
then reads three predictions of every step against the reference's
(``precision="highest"``), each on the contexts the engine's own latencies
make: the reference with its matmul operands rounded to bfloat16 (the
chip's pass), the reference in fp8 (e4m3, the cell's control), and the
engine itself.  One JSON line per seed:

    JAX_PLATFORMS=cpu PYTHONPATH=src python bench/simnet_emulate.py \\
        --program xal --n 9088 --seeds 11 12 13
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench.harness import derive_seed  # noqa: E402
from bench.reference import simnet as ref  # noqa: E402

WIDTHS = {"window": 129, "channels": 128, "n_conv": 3, "kernel_size": 5, "hidden": 128,
          "feat_dim": 44, "subtrace": 1024}


def bf16_forward(params, x, prec=None, quant=False):
    """The reference's forward with bfloat16 operands and float32 sums."""
    if quant:
        return FORWARD(params, x, quant=True)

    def mm(a, b):
        return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)

    h = x
    B, W, _ = x.shape
    for conv in params["convs"]:
        k = conv["w"].shape[0]
        hp = jnp.pad(h, ((0, 0), (k // 2, k // 2), (0, 0)))
        h = ref._gelu(sum(mm(hp[:, i: i + W], conv["w"][i]) for i in range(k)) + conv["b"])
    h = ref._gelu(mm(h.reshape(B, -1), params["fc"]["w"]) + params["fc"]["b"])
    return mm(h, params["head"]["w"]) + params["head"]["b"]


FORWARD = ref.forward


def readings(rows, seed: int, block: int) -> dict:
    from repro.api import TrainedModel
    from repro.core.simnet import SimNetConfig

    params = jax.jit(lambda k: ref.init_params(k, WIDTHS))(
        jax.random.PRNGKey(derive_seed(seed, "weights")))
    r = TrainedModel(params=params, cfg=SimNetConfig()).engine(batch_size=8).simulate(rows)
    lat = r.latencies()
    hi = ref.teacher(params, rows, lat, WIDTHS, block=block)
    out = {"seed": seed, "predictions": len(hi["raw"]), "context_rows": r.metrics["context_rows"],
           "engine_f32": ref.latency_gap(hi["raw"], hi["lat"])}
    ref._JITS.clear()
    ref.forward = bf16_forward
    try:
        bf = ref.teacher(params, rows, lat, WIDTHS, block=block)
    finally:
        ref.forward = FORWARD
        ref._JITS.clear()
    q = ref.teacher(params, rows, lat, WIDTHS, quant=True, block=block)
    out.update(bf16=ref.latency_gap(hi["raw"], ref.round_lat(bf["raw"])),
               bf16_err_max=float(np.abs(bf["raw"] - hi["raw"]).max()),
               fp8=ref.latency_gap(hi["raw"], ref.round_lat(q["raw"])))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--program", default="xal")
    ap.add_argument("--n", type=int, default=9088, help="instructions simulated")
    ap.add_argument("--skip", type=int, default=5000, help="instructions skipped first")
    ap.add_argument("--seeds", type=int, nargs="+", default=[11])
    ap.add_argument("--block", type=int, default=512)
    args = ap.parse_args()
    import repro.uarch as U
    from repro.core.align import build_adjusted_trace

    prog = U.get_benchmark(args.program)
    det, _ = U.run_detailed(prog, U.run_functional(prog, args.skip + args.n), U.UARCH_C)
    rows = build_adjusted_trace(det).adjusted[args.skip: args.skip + args.n]
    for seed in args.seeds:
        print(json.dumps(readings(rows, seed, args.block)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
