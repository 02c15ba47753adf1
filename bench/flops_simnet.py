"""Operations and bytes of SimNet's device work, from shapes alone.

``w`` is the configuration's dict (``bench/configs/simnet-c3.json``).  A
multiply-add counts two operations.  Per prediction (one instruction in
one scan iteration) the C3 stack runs over ``window`` rows:

  convolutions  2 * window * kernel_size * c_in * channels each (c_in is
                feat_dim for the first, channels after)
  dense         2 * window * channels * hidden (the flattened rows)
  head          2 * hidden * 2

At the configuration's widths that is 7.27 + 2 x 21.14 + 4.23 + 0.0005 ≈
53.8 MFLOP.  The feature expansion, masks and clock are elementwise and
left out.

Bytes of one scan launch of ``rows`` sub-traces and ``iterations`` steps:
every iteration reads the weights (float32) and reads and writes each row's
carry (the newest ``window - 1`` instructions' two packed words and three
cycle counts, and four scalars); the launch reads its packed input (two
int32 words per position) and writes every prediction's two int32
latencies.  Activations that stay on the chip are not counted.
"""
from __future__ import annotations

from typing import Dict


def forward_flops_per_prediction(w: Dict) -> int:
    W, k, ch, hid = w["window"], w["kernel_size"], w["channels"], w["hidden"]
    convs = 0
    cin = w["feat_dim"]
    for _ in range(w["n_conv"]):
        convs += 2 * W * k * cin * ch
        cin = ch
    return convs + 2 * W * ch * hid + 2 * hid * 2


def param_bytes(w: Dict) -> int:
    W, k, ch, hid = w["window"], w["kernel_size"], w["channels"], w["hidden"]
    n, cin = 0, w["feat_dim"]
    for _ in range(w["n_conv"]):
        n += k * cin * ch + ch
        cin = ch
    return 4 * (n + W * ch * hid + hid + hid * 2 + 2)


def scan_cost(w: Dict, rows: int, iterations: int) -> Dict[str, float]:
    """One scan launch: FLOPs and bytes."""
    K = w["window"] - 1
    carry = 4 * (K * (2 + 3) + 4)
    per_iteration = param_bytes(w) + 2 * rows * carry
    return {"flops": float(rows * iterations * forward_flops_per_prediction(w)),
            "bytes": float(iterations * per_iteration + rows * iterations * (8 + 8))}
