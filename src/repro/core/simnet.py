"""SimNet (Li et al., SIGMETRICS'22, arXiv:2105.05821), the DL-based
simulator Tao compares against, in its C3 configuration.

How it differs from Tao:

  * INPUT: µarch-specific features read from a detailed trace of the design
    point (data-access level, branch mispredict, icache miss, TLB miss)
    beside the static ones, so every new design point needs a new detailed
    trace (the regeneration cost Table 4 charges it for).
  * CONTEXT: an instruction is predicted from itself and the instructions
    still in flight when it reaches fetch, each with how long it has been in
    the processor.  Those times come from the latencies already predicted,
    so simulation is sequential: instruction i+1 waits for instruction i.
    Parallelism comes from cutting a trace into sub-traces simulated side by
    side, each warmed on the instructions before it (``engine/sequential.py``).
  * OUTPUT: the instruction's fetch and completion latencies.

The arXiv paper's tables are not in this repository; the widths below are
this repository's reading of C3.  Input: ``window`` = context + 1 rows of
``feat_dim`` features; row 0 is the current instruction, rows 1.. the
in-flight ones, newest first, zero rows where fewer are in flight.  Three
1-D convolutions over the rows (kernel 5, "same" zero padding, GELU), their
output flattened into a dense layer (GELU) so the whole context reaches the
prediction, and a dense head to the two latencies in units of ``LAT_SCALE``.
SimNet's third output, store latency, is left out: the detailed trace has
no store label.

Clock (``trajectory``): latencies round to whole cycles, f = max(0, round(pf))
and c = max(1, round(pc)), both at most ``MAX_LATENCY``; F_i = F_{i-1} + f_i,
C_i = F_i + c_i and R_i = max(C_i, R_{i-1}) (in-order retire).  Instruction
i's context is taken when it reaches fetch, at T_i = F_{i-1} (its own f_i is
what is being predicted): the j < i with R_j > T_i, at most the newest
``context``.  Each context row carries T_i - F_j, [C_j <= T_i] and
max(0, T_i - C_j), the cycle counts over ``LAT_SCALE``.

``context_inputs`` builds the model input from given latencies: the
engine's scan calls it with predicted ones, training (``teacher_inputs``)
with the detailed trace's true ones.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from ..nn.core import dense, gelu, init_dense, truncated_normal_init
from ..train.optim import AdamWConfig, adamw_update
from .model import LAT_SCALE

__all__ = [
    "MAX_LATENCY",
    "ROW_FIELDS",
    "SimNetConfig",
    "context_inputs",
    "in_flight",
    "init_simnet",
    "make_simnet_step",
    "pack_rows",
    "round_latencies",
    "simnet_forward",
    "static_features",
    "teacher_inputs",
    "timing_features",
    "trajectory",
    "true_latencies",
]

# per-row static features: opcode one-hot 15, registers 3, flags 4,
# µarch events 7 (data-access level one-hot 4, mispredict, icache miss,
# TLB miss), address 1; then the context rows' three timing features
STATIC_DIM = 30
TIMING_DIM = 3
# a guard on the rounded latencies: the int32 clock of a sub-trace cannot
# overflow (the detailed simulator's latencies stay in the hundreds)
MAX_LATENCY = 1 << 16

# the adjusted (detailed-trace) fields a row needs
ROW_FIELDS = ("opcode", "dst", "src1", "src2", "is_branch", "taken", "is_mem",
              "is_store", "dlevel", "mispred", "icache_miss", "tlb_miss", "addr")

# packed word 0: bit offset and width of each field; bit 28 marks a real
# instruction (an all-zero word is no instruction).  Word 1: addr mod 2^20.
_LAYOUT = (("opcode", 0, 4), ("dst", 4, 5), ("src1", 9, 5), ("src2", 14, 5),
           ("is_branch", 19, 1), ("taken", 20, 1), ("is_mem", 21, 1),
           ("is_store", 22, 1), ("dlevel", 23, 2), ("mispred", 25, 1),
           ("icache_miss", 26, 1), ("tlb_miss", 27, 1))
VALID_BIT = 28
_ADDR_BITS = 20


@dataclasses.dataclass(frozen=True)
class SimNetConfig:
    window: int = 129        # input rows: the current instruction + context
    channels: int = 128
    n_conv: int = 3          # the C3 configuration
    kernel_size: int = 5
    hidden: int = 128        # the dense layer over the flattened rows
    feat_dim: int = 44       # 30 static + 3 timing, zero-padded
    subtrace: int = 1024     # counted instructions per simulated sub-trace

    @property
    def context(self) -> int:
        """In-flight rows: the design point's ROB (128 for UARCH_C)."""
        return self.window - 1

    @property
    def warmup(self) -> int:
        """Instructions a sub-trace runs before its first counted one: the
        ROB's depth, so the context can fill."""
        return self.context


def pack_rows(rows: np.ndarray) -> np.ndarray:
    """A detailed trace's rows (``ROW_FIELDS``) as (n, 2) int32 words, 8 B
    per instruction; ``static_features`` expands them on the device."""
    w0 = np.full(len(rows), 1 << VALID_BIT, np.int32)
    for name, shift, _ in _LAYOUT:
        w0 |= rows[name].astype(np.int32) << shift
    w1 = (rows["addr"] & ((1 << _ADDR_BITS) - 1)).astype(np.int32)
    return np.stack([w0, w1], axis=1)


def static_features(words):
    """(..., 2) packed words -> (..., STATIC_DIM) float32 features."""
    w = words[..., 0]

    def bit(name):
        _, shift, width = next(f for f in _LAYOUT if f[0] == name)
        return (w >> shift) & ((1 << width) - 1)

    f32 = jnp.float32
    parts = [
        jax.nn.one_hot(bit("opcode"), 15, dtype=f32),
        jnp.stack([bit(k) for k in ("dst", "src1", "src2")], -1).astype(f32) / 32.0,
        jnp.stack([bit(k) for k in ("is_branch", "taken", "is_mem", "is_store")], -1).astype(f32),
        jax.nn.one_hot(bit("dlevel"), 4, dtype=f32),
        jnp.stack([bit(k) for k in ("mispred", "icache_miss", "tlb_miss")], -1).astype(f32),
        (words[..., 1].astype(f32) / float(1 << _ADDR_BITS))[..., None],
    ]
    return jnp.concatenate(parts, -1)


def is_valid(words):
    """Which packed words hold an instruction."""
    return ((words[..., 0] >> VALID_BIT) & 1) > 0


def in_flight(R, T):
    """The context slots whose instruction has not retired by ``T``: an
    empty slot holds R = -1."""
    return R > T[..., None]


def timing_features(T, F, C):
    """Per context slot: cycles since its fetch, whether it has completed,
    cycles since its completion (or 0); cycle counts over ``LAT_SCALE``."""
    T = T[..., None]
    return jnp.stack([(T - F).astype(jnp.float32) / LAT_SCALE,
                      (C <= T).astype(jnp.float32),
                      jnp.maximum(T - C, 0).astype(jnp.float32) / LAT_SCALE], -1)


def context_inputs(cur, ctx, F, C, R, T, cfg: SimNetConfig):
    """The model input of the instructions ``cur`` (..., 2) reaching fetch
    at ``T`` (...), with the newest ``cfg.context`` earlier instructions
    ``ctx`` (..., K, 2) and their fetch, completion and retire cycles
    (..., K), newest first.  Returns (x (..., window, feat_dim), in-flight
    mask (..., K))."""
    mask = in_flight(R, T)
    rows = jnp.concatenate([static_features(ctx), timing_features(T, F, C)], -1)
    rows = jnp.where(mask[..., None], rows, 0.0)
    head = static_features(cur)
    head = jnp.concatenate([head, jnp.zeros(head.shape[:-1] + (TIMING_DIM,), head.dtype)], -1)
    x = jnp.concatenate([head[..., None, :], rows], -2)
    pad = cfg.feat_dim - x.shape[-1]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]), mask


def trajectory(lat):
    """(..., n, 2) integer (f, c) latencies of one sequence -> its fetch,
    completion and retire cycles F, C, R, each (..., n)."""
    F = jnp.cumsum(lat[..., 0], -1)
    C = F + lat[..., 1]
    return F, C, jax.lax.cummax(C, axis=C.ndim - 1)


def teacher_inputs(words, lat, idx, cfg: SimNetConfig):
    """Model inputs of the instructions ``idx`` of one sequence ``words``
    (n, 2) whose latencies ``lat`` (n, 2) are given (training: the detailed
    trace's; a check: the program's).  Returns (x, in-flight mask)."""
    F, C, R = trajectory(lat)
    T = jnp.where(idx > 0, F[jnp.maximum(idx - 1, 0)], 0)
    j = idx[:, None] - 1 - jnp.arange(cfg.context)[None, :]
    ok = j >= 0
    jj = jnp.maximum(j, 0)
    return context_inputs(words[idx], words[jj], F[jj], C[jj], jnp.where(ok, R[jj], -1), T, cfg)


def round_latencies(pred):
    """(..., 2) predictions in ``LAT_SCALE`` units -> int32 (f, c) cycles."""
    cyc = jnp.round(pred * LAT_SCALE)
    f = jnp.clip(cyc[..., 0], 0, MAX_LATENCY)
    c = jnp.clip(cyc[..., 1], 1, MAX_LATENCY)
    return jnp.stack([f, c], -1).astype(jnp.int32)


def init_simnet(key, cfg: SimNetConfig) -> Dict:
    ks = jax.random.split(key, cfg.n_conv + 2)
    convs = []
    cin = cfg.feat_dim
    for i in range(cfg.n_conv):
        std = 1.0 / np.sqrt(cfg.kernel_size * cin)
        convs.append({"w": truncated_normal_init(ks[i], (cfg.kernel_size, cin, cfg.channels), std),
                      "b": jnp.zeros((cfg.channels,))})
        cin = cfg.channels
    return {"convs": convs,
            "fc": init_dense(ks[-2], cfg.window * cfg.channels, cfg.hidden),
            "head": init_dense(ks[-1], cfg.hidden, 2)}


def simnet_forward(params: Dict, x: jnp.ndarray) -> jnp.ndarray:
    """x (B, window, feat_dim) -> (B, 2) fetch and completion latency of
    each row's current instruction, in ``LAT_SCALE`` units."""
    h = x
    for conv in params["convs"]:
        p = conv["w"].shape[0] // 2
        h = jax.lax.conv_general_dilated(
            h, conv["w"], window_strides=(1,), padding=[(p, p)],
            dimension_numbers=("NWC", "WIO", "NWC"))
        h = gelu(h + conv["b"])
    h = gelu(dense(params["fc"], h.reshape(h.shape[0], -1)))
    return dense(params["head"], h)


def make_simnet_step(cfg: SimNetConfig, opt_cfg: AdamWConfig):
    """One AdamW step on teacher-forced inputs: ``batch`` holds ``x``
    (``teacher_inputs``) and ``labels``, the true (f, c) cycles."""

    def loss_fn(params, batch):
        pred = simnet_forward(params, batch["x"])
        return jnp.mean(jnp.square(pred - batch["labels"] / LAT_SCALE))

    @jax.jit
    def step(params, opt, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        params, opt, _ = adamw_update(params, grads, opt, opt_cfg)
        return params, opt, loss

    return step


def true_latencies(rows: np.ndarray) -> np.ndarray:
    """A detailed trace's (adjusted) fetch and completion latencies, (n, 2)
    int32: the labels, and the trajectory teacher-forced training reads."""
    return np.stack([rows["fetch_lat"], rows["exec_lat"]], 1).astype(np.int32)
