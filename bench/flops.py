"""Operations and bytes of Tao's device work, from shapes alone.

``w`` is a configuration file's dict (``bench/configs/*.json``).  A multiply-
add counts two operations.  Attention is counted as the einsum computes it:
every query against all ``window`` keys, causal mask or not.

Forward, per simulated instruction (every position of a window runs the
whole model):

  embed     dense layers of regbits, flags, brhist, memdist (the opcode
            table is a gather) and the combine layer over 5 * d_cat
  adapt     d_model x d_model
  blocks    qkv 3 d^2, proj d^2, up and down 2 d d_ff, and attention
            2 * window * d (scores and the weighted sum) per layer
  heads     d_model x (2 * lat_buckets + 1 + dlevels + 1 + 1)

Training with the embedding frozen (§4.3 transfer): the embedding runs
forward only; adapt and the prediction network run forward, backward to
their inputs and backward to their weights (3x forward), except that no
gradient flows into the embedding, so adapt's input gradient is not needed
(2x forward for adapt).

The fused extraction kernel (``kernels/fused``) reads per position 4 SMEM
words (bucket, addr, outcome, is_mem) and 8 int32 lanes (opcode, dst,
src1, src2, is_branch, taken, is_mem, is_store), 48 B, and writes float32
regbits, flags, brhist and raw memdist deltas, 4 * (num_regs + flags_dim +
n_queue + n_mem) B; once per call it moves the branch table
(n_buckets x 128 lanes, float32) and the address queue (2 x 128 int32) in
and out.  Its arithmetic is integer compares and shifts, which the peak
FLOP/s does not bound, so its roofline is its bytes.
"""
from __future__ import annotations

from typing import Dict

LANES = 128


def _lanes(n: int) -> int:
    return -(-n // LANES) * LANES


def forward_flops_parts(w: Dict) -> Dict[str, int]:
    d, c, f = w["d_model"], w["d_cat"], w["d_ff"]
    embed = c * (w["num_regs"] + w["flags_dim"] + w["n_queue"] + w["n_mem"]) + 5 * c * d
    adapt = d * d
    block = 3 * d * d + d * d + 2 * d * f + 2 * w["window"] * d
    heads = d * (2 * w["lat_buckets"] + 1 + w["dlevels"] + 1 + 1)
    return {"embed": 2 * embed, "adapt": 2 * adapt,
            "pred": 2 * (w["n_layers"] * block + heads)}


def forward_flops_per_instruction(w: Dict) -> int:
    return sum(forward_flops_parts(w).values())


def train_flops_per_window(w: Dict) -> int:
    """Frozen-embedding transfer step: forward + backward of adapt and pred."""
    p = forward_flops_parts(w)
    return w["window"] * (p["embed"] + 2 * p["adapt"] + 3 * p["pred"])


def embed_bytes(w: Dict) -> int:
    c, d = w["d_cat"], w["d_model"]
    return 4 * (w["num_opcodes"] * c + (w["num_regs"] + 1) * c + (w["flags_dim"] + 1) * c
                + (w["n_queue"] + 1) * c + (w["n_mem"] + 1) * c + (5 * c + 1) * d)


def param_bytes(w: Dict) -> int:
    """float32 bytes of one model (embed + adapt + pred)."""
    d, f = w["d_model"], w["d_ff"]
    block = 2 * 2 * d + (d + 1) * 3 * d + (d + 1) * d + (d + 1) * f + (f + 1) * d
    heads = (d + 1) * (2 * w["lat_buckets"] + 1 + w["dlevels"] + 1 + 1)
    pred = w["window"] * d + w["n_layers"] * block + 2 * d + heads
    return embed_bytes(w) + 4 * ((d + 1) * d + pred)


def kernel_bytes_per_instruction(w: Dict) -> Dict[str, int]:
    read = 4 * 4 + 8 * 4
    write = 4 * (w["num_regs"] + w["flags_dim"] + w["n_queue"] + w["n_mem"])
    return {"read": read, "write": write}


def kernel_bytes_per_call(w: Dict, positions: int) -> int:
    per = kernel_bytes_per_instruction(w)
    carry = 4 * (w["n_buckets"] * _lanes(w["n_queue"]) + 2 * _lanes(w["n_mem"]))
    return positions * (per["read"] + per["write"]) + 2 * carry


def step_cost(w: Dict) -> Dict[str, float]:
    """One engine step: ``batch_size`` windows through the forward plus the
    metric fold.  Bytes: the weights once, the model inputs (float32 features
    and int32 opcode) in, and the small carry."""
    pos = w["batch_size"] * w["window"]
    inputs = pos * 4 * (1 + w["num_regs"] + w["flags_dim"] + w["n_queue"] + w["n_mem"] + 3)
    return {"flops": float(pos * forward_flops_per_instruction(w)),
            "bytes": float(param_bytes(w) + inputs)}


def train_step_cost(w: Dict, batch: int) -> Dict[str, float]:
    """One transfer step: the frozen-embedding forward/backward over
    ``batch`` windows (features and 8 label columns in), then AdamW over
    adapt + pred: params, two moments and the gradient read, params and
    moments written (7 passes over the trainable bytes)."""
    pos = batch * w["window"]
    inputs = pos * 4 * (1 + w["num_regs"] + w["flags_dim"] + w["n_queue"] + w["n_mem"] + 8)
    head = param_bytes(w) - embed_bytes(w)
    return {"flops": float(batch * train_flops_per_window(w)),
            "bytes": float(embed_bytes(w) + 7 * head + inputs)}


def least_seconds(flops: float, nbytes: float, peak: Dict) -> float:
    """The roofline: the larger of compute time and memory time."""
    return max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
