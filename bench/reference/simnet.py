"""Plain jax.numpy reference of SimNet's C3 model and its sequential
simulation (arXiv:2105.05821 as this repository reads it), independent of
the simulator's code.

``w`` is the configuration's dict: ``window`` input rows (the current
instruction, then ``window - 1`` context rows), ``channels``, ``n_conv``,
``kernel_size``, ``hidden``, ``feat_dim``, ``subtrace``.

Model: each row holds the instruction's static features (opcode one-hot
15, registers / 32, branch / taken / memory / store flags, data-access
level one-hot 4, mispredict, icache miss, TLB miss, address mod 2^20 over
2^20: 30) and, for context rows, cycles since its fetch, whether it has
completed and cycles since its completion (or 0), zero-padded to
``feat_dim``.  Three convolutions over the rows (kernel ``kernel_size``,
zero "same" padding, written as one matrix product per tap), each with
tanh-GELU; the rows flattened into a dense layer with GELU; a dense head to
the fetch and completion latencies in cycles.

Simulation: f = max(0, round(fetch)) and c = max(1, round(completion)),
both at most 2^16; F_i = F_{i-1} + f_i, C_i = F_i + c_i, R_i = max(C_i,
R_{i-1}).  Instruction i reaches fetch at T_i = F_{i-1} (0 for the first of
a sequence); its context is the newest ``window - 1`` earlier instructions
of its sequence with R_j > T_i, newest first.  A trace of n instructions
is ceil(n / subtrace) sub-traces; sub-trace k counts [k * subtrace,
(k + 1) * subtrace) and runs the ``window - 1`` instructions before them
first, uncounted; its cycles run from T of its first counted instruction
to R of its last; CPI is their sum over n.

Every matrix product runs at ``precision`` (``highest``: float32 on the
TPU too); the control (``quant``) runs them as fp8 (e4m3) matmuls.
"""
from __future__ import annotations

import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from .model import _q8

MAX_LATENCY = 1 << 16
STATIC = 30


# ---------------------------------------------------------------------------
# rows and weights
# ---------------------------------------------------------------------------


def features(rows: np.ndarray) -> np.ndarray:
    """(n, 30) float32 static features of a detailed trace's rows."""
    n = len(rows)
    out = np.zeros((n, STATIC), np.float32)
    i = np.arange(n)
    out[i, rows["opcode"].astype(np.int64)] = 1.0
    for k, f in enumerate(("dst", "src1", "src2")):
        out[:, 15 + k] = rows[f].astype(np.float32) / 32.0
    for k, f in enumerate(("is_branch", "taken", "is_mem", "is_store")):
        out[:, 18 + k] = rows[f]
    out[i, 22 + rows["dlevel"].astype(np.int64)] = 1.0
    for k, f in enumerate(("mispred", "icache_miss", "tlb_miss")):
        out[:, 26 + k] = rows[f]
    out[:, 29] = (rows["addr"].astype(np.int64) % (1 << 20)) / float(1 << 20)
    return out


def _dense(key, i, o, scale=1.0):
    w = jax.random.truncated_normal(key, -2.0, 2.0, (i, o), jnp.float32)
    return {"w": w * (scale / math.sqrt(i) / 0.87962566103423978), "b": jnp.zeros((o,))}


# Weights from the seed: fan-in scaled, with a gain of 3 on the
# convolutions and the dense layer (at gain 1 the GELU stack shrinks every
# prediction onto the head's bias, so no latency would depend on its
# context); the head at a quarter of the fan-in scale, its weights centred over
# the hidden units (the GELU outputs' common positive part then moves no
# prediction, so no seed's model stalls fetch at 0 cycles and fills the
# context), its bias an instruction fetched every 3 cycles and completing
# 16 cycles later.  Over a detailed trace every seed's model keeps CPI at
# a few cycles and 4-24 instructions in flight.
GAIN = 3.0
HEAD_GAIN = 0.25
HEAD_BIAS = (3.0, 16.0)


def init_params(key, w: Dict) -> Dict:
    k = jax.random.split(key, w["n_conv"] + 2)
    convs, cin = [], w["feat_dim"]
    for i in range(w["n_conv"]):
        d = _dense(k[i], w["kernel_size"] * cin, w["channels"], GAIN)
        convs.append({"w": d["w"].reshape(w["kernel_size"], cin, w["channels"]), "b": d["b"]})
        cin = w["channels"]
    head = _dense(k[-1], w["hidden"], 2, HEAD_GAIN)
    head["w"] = head["w"] - head["w"].mean(0)
    head["b"] = jnp.asarray(HEAD_BIAS, jnp.float32)
    return {"convs": convs, "fc": _dense(k[-2], w["window"] * w["channels"], w["hidden"], GAIN),
            "head": head}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def forward(params: Dict, x, *, prec=jax.lax.Precision.HIGHEST, quant: bool = False):
    """x (B, rows, feat_dim) -> (B, 2) fetch and completion latency."""

    def mm(a, b):
        if quant:
            return jnp.matmul(_q8(a, -1), _q8(b, 0), precision=jax.lax.Precision.HIGHEST)
        return jnp.matmul(a, b, precision=prec)

    h = x
    B, W, _ = x.shape
    for conv in params["convs"]:
        k = conv["w"].shape[0]
        hp = jnp.pad(h, ((0, 0), (k // 2, k // 2), (0, 0)))
        h = _gelu(sum(mm(hp[:, i: i + W], conv["w"][i]) for i in range(k)) + conv["b"])
    h = _gelu(mm(h.reshape(B, -1), params["fc"]["w"]) + params["fc"]["b"])
    return mm(h, params["head"]["w"]) + params["head"]["b"]


def round_lat(raw: np.ndarray) -> np.ndarray:
    """Predictions (..., 2) -> integer (f, c) cycles."""
    r = np.round(np.asarray(raw, np.float64))
    return np.stack([np.clip(r[..., 0], 0, MAX_LATENCY),
                     np.clip(r[..., 1], 1, MAX_LATENCY)], -1).astype(np.int64)


def _inputs(static, inst, T, F, C, R, first, p, W: int, feat: int):
    """The inputs of predictions ``p`` of a flattened set of sequences:
    prediction q runs instruction ``inst[q]`` at clock ``T[q]``; its
    sequence starts at prediction ``first[q]``."""
    K = W - 1
    j = p[:, None] - 1 - jnp.arange(K)[None, :]
    ok = j >= first[p][:, None]
    j = jnp.maximum(j, 0)
    t = T[p][:, None]
    live = ok & (R[j] > t)
    ctx = jnp.concatenate([static[inst[j]],
                           (t - F[j]).astype(jnp.float32)[..., None],
                           (C[j] <= t).astype(jnp.float32)[..., None],
                           jnp.maximum(t - C[j], 0).astype(jnp.float32)[..., None]], -1)
    ctx = jnp.where(live[..., None], ctx, 0.0)
    cur = jnp.concatenate([static[inst[p]], jnp.zeros((p.shape[0], 3), jnp.float32)], -1)
    x = jnp.concatenate([cur[:, None], ctx], 1)
    return jnp.pad(x, ((0, 0), (0, 0), (0, feat - x.shape[-1]))), live.sum(1)


_JITS: Dict = {}


def _block_fn(W: int, feat: int, quant: bool):
    key = (W, feat, quant)
    if key not in _JITS:
        def fn(params, static, inst, T, F, C, R, first, p):
            x, _ = _inputs(static, inst, T, F, C, R, first, p, W, feat)
            return forward(params, x, quant=quant)

        _JITS[key] = jax.jit(fn)
    return _JITS[key]


# ---------------------------------------------------------------------------
# the sub-trace plan and the teacher-forced check
# ---------------------------------------------------------------------------


def plan(n: int, w: Dict) -> List[np.ndarray]:
    """Per sub-trace, the instruction index of each iteration (-1: none)."""
    warm, sub = w["window"] - 1, w["subtrace"]
    out = []
    for k in range(-(-n // sub)):
        idx = k * sub - warm + np.arange(warm + sub)
        out.append(np.where((idx >= 0) & (idx < n), idx, -1))
    return out


def replay(lat: np.ndarray, n: int, w: Dict) -> Dict:
    """The clock of every sub-trace run on given latencies ``lat`` (S, warm
    + subtrace, 2), flattened over the sub-traces' instructions in order:
    ``inst``, ``T``, ``F``, ``C``, ``R``, ``first`` per prediction, the
    given latencies there, and the cycles and CPI they make."""
    warm = w["window"] - 1
    parts = {k: [] for k in ("inst", "T", "F", "C", "R", "first", "lat", "counted")}
    cycles, base = 0, 0
    for k, idx in enumerate(plan(n, w)):
        v = idx >= 0
        lk = lat[k][v].astype(np.int64)
        F = np.cumsum(lk[:, 0])
        C = F + lk[:, 1]
        R = np.maximum.accumulate(C)
        T = np.concatenate([[0], F[:-1]])
        counted = (np.arange(len(idx)) >= warm)[v]
        cycles += int(R[-1] - T[counted][0])
        for name, a in (("inst", idx[v]), ("T", T), ("F", F), ("C", C), ("R", R),
                        ("first", np.full(len(T), base)), ("lat", lk), ("counted", counted)):
            parts[name].append(a)
        base += len(T)
    out = {k: np.concatenate(v) for k, v in parts.items()}
    out.update(cycles=cycles, cpi=cycles / n)
    return out


def teacher(params: Dict, rows: np.ndarray, lat: np.ndarray, w: Dict, *, quant: bool = False,
            block: int = 2048) -> Dict:
    """Every prediction of a simulation whose latencies were ``lat``,
    recomputed from contexts rebuilt from those latencies: ``raw`` (P, 2),
    ``lat`` the given latencies there (P, 2), and ``replay``'s clock."""
    n = len(rows)
    rp = replay(lat, n, w)
    P = len(rp["T"])
    dev = {k: jnp.asarray(rp[k].astype(np.int32)) for k in ("inst", "T", "F", "C", "R", "first")}
    static = jnp.asarray(features(rows))
    fn = _block_fn(w["window"], w["feat_dim"], quant)
    raw = []
    for lo in range(0, P, block):
        p = jnp.minimum(lo + jnp.arange(block), P - 1)
        raw.append(np.asarray(fn(params, static, dev["inst"], dev["T"], dev["F"], dev["C"],
                                 dev["R"], dev["first"], p)))
    rp["raw"] = np.concatenate(raw)[:P]
    return rp


def latency_gap(raw: np.ndarray, got: np.ndarray) -> float:
    """The least tolerance d, in cycles, at which every latency ``got`` (P,
    2) lies in the reference's rounding range [round(raw - d), round(raw +
    d)] (each clipped as ``round_lat`` clips)."""
    r = np.asarray(raw, np.float64)
    g = np.asarray(got, np.float64)
    d = np.maximum(g - 0.5 - r, r - g - 0.5)
    d = np.where(g == round_lat(raw), 0.0, np.maximum(d, 0.0))
    return float(d.max()) if d.size else 0.0


def rollout(params: Dict, rows: np.ndarray, w: Dict) -> Dict:
    """The sequential simulation itself, one instruction at a time in every
    sub-trace (tiny sizes only): ``lat`` (S, warm + subtrace, 2), zeros
    where no instruction ran, and the CPI."""
    n = len(rows)
    static = features(rows)
    W, feat = w["window"], w["feat_dim"]
    K = W - 1
    idx = np.stack(plan(n, w))
    S, L = idx.shape
    lat = np.zeros((S, L, 2), np.int64)
    hist = [[] for _ in range(S)]   # per sub-trace: (instruction, F, C, R), oldest first
    clock = np.zeros(S, np.int64)
    last = np.zeros(S, np.int64)
    fwd = jax.jit(forward)
    for t in range(L):
        x = np.zeros((S, W, feat), np.float32)
        for k in range(S):
            if idx[k, t] < 0:
                continue
            x[k, 0, :STATIC] = static[idx[k, t]]
            live = [h for h in hist[k][::-1][:K] if h[3] > clock[k]]
            for r, (i, F, C, _) in enumerate(live):
                x[k, 1 + r, :STATIC] = static[i]
                x[k, 1 + r, STATIC:STATIC + 3] = (clock[k] - F, C <= clock[k], max(clock[k] - C, 0))
        pred = round_lat(np.asarray(fwd(params, jnp.asarray(x))))
        for k in range(S):
            if idx[k, t] < 0:
                continue
            lat[k, t] = pred[k]
            F = clock[k] + pred[k, 0]
            C = F + pred[k, 1]
            R = max(C, last[k])
            hist[k].append((idx[k, t], F, C, R))
            clock[k], last[k] = F, R
    return {"lat": lat, "cpi": replay(lat, n, w)["cpi"]}
