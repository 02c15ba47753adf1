"""Plain jax.numpy reference of Tao's model, loss and optimizer
(arXiv:2404.10921 §4.2-4.3), independent of the simulator's code.

Model: per-category embeddings (an opcode table; linear layers for the
register bitmap, flags, branch history and memory distance), concatenated
and combined by a linear layer with tanh-GELU; a per-µarch linear
adaptation; learned positions; pre-LayerNorm causal self-attention blocks
(GELU MLP); a final LayerNorm; heads for fetch and exec latency (bucket
logits, decoded as the representative of the most likely bucket), branch
mispredict (logit), data-access level (logits) and icache / TLB miss
(logits).

Every matrix product runs at ``precision`` (``highest``: float32 on the
TPU too); the training control (``quant``) runs the dense layers as fp8
matmuls instead, forward and backward.  The parameter tree is the one the
simulator consumes, so the benchmark makes weights here, from the seed,
and hands the same tree to both.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

# latency bucket edges and representatives (cycles)
LAT_EDGES = np.array([0, 1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48, 64, 96, 128, 192], np.float32)
LAT_REPS = np.concatenate([LAT_EDGES[:-1] + (np.diff(LAT_EDGES) - 1) / 2.0, [256.0]]).astype(np.float32)
LOSS_WEIGHTS = {"fetch_lat": 1.0, "exec_lat": 1.0, "mispred": 0.5, "dlevel": 0.5,
                "icache_miss": 0.25, "tlb_miss": 0.25}
DLEVEL_L2 = 2  # access levels: none, L1, L2, memory; >= L2 is an L1D miss


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def _dense(key, i, o, scale=1.0):
    w = jax.random.truncated_normal(key, -2.0, 2.0, (i, o), jnp.float32)
    return {"w": w * (scale / math.sqrt(i) / 0.87962566103423978), "b": jnp.zeros((o,))}


def _ln(d):
    return {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))}


def init_embed(key, w: Dict) -> Dict:
    k = jax.random.split(key, 6)
    c = w["d_cat"]
    table = jax.random.truncated_normal(k[0], -2.0, 2.0, (w["num_opcodes"], c)) / 0.87962566103423978
    return {
        "opcode": {"table": table},
        "regbits": _dense(k[1], w["num_regs"], c),
        "flags": _dense(k[2], w["flags_dim"], c),
        "brhist": _dense(k[3], w["n_queue"], c),
        "memdist": _dense(k[4], w["n_mem"], c),
        "combine": _dense(k[5], 5 * c, w["d_model"]),
    }


def init_head(key, w: Dict) -> Dict:
    """The per-µarch groups: ``adapt`` and ``pred``."""
    d, L = w["d_model"], w["n_layers"]
    k = jax.random.split(key, L + 8)
    blocks = []
    for i in range(L):
        kb = jax.random.split(k[i], 4)
        blocks.append({
            "ln1": _ln(d),
            "qkv": _dense(kb[0], d, 3 * d),
            "proj": _dense(kb[1], d, d, 1.0 / math.sqrt(2 * L)),
            "ln2": _ln(d),
            "up": _dense(kb[2], d, w["d_ff"]),
            "down": _dense(kb[3], w["d_ff"], d, 1.0 / math.sqrt(2 * L)),
        })
    adapt = jnp.eye(d) + 0.01 * jax.random.normal(k[L], (d, d))
    return {
        "adapt": {"w": adapt, "b": jnp.zeros((d,))},
        "pred": {
            "pos": 0.02 * jax.random.normal(k[L + 1], (w["window"], d)),
            "blocks": blocks,
            "ln_f": _ln(d),
            "head_lat": _dense(k[L + 2], d, 2 * len(LAT_EDGES)),
            "head_branch": _dense(k[L + 3], d, 1),
            "head_dlevel": _dense(k[L + 4], d, w["dlevels"]),
            "head_icache": _dense(k[L + 5], d, 1),
            "head_tlb": _dense(k[L + 6], d, 1),
        },
    }


def init_params(key, w: Dict) -> Dict:
    ke, kh = jax.random.split(key)
    return {"embed": init_embed(ke, w), **init_head(kh, w)}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _q8(x, axis):
    """fp8 (e4m3) fake quantisation, scaled along ``axis`` (per row of an
    activation, per output channel of a weight) so the largest magnitude
    maps to 448, with a straight-through gradient."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


@jax.custom_vjp
def _qmatmul(x, w):
    """``x @ w`` with both operands in fp8, and in the backward pass the
    incoming gradient too: an fp8 matmul in training."""
    return jnp.matmul(_q8(x, -1), _q8(w, 0), precision=jax.lax.Precision.HIGHEST)


def _qmatmul_fwd(x, w):
    return _qmatmul(x, w), (x, w)


def _qmatmul_bwd(res, g):
    x, w = res
    hp = jax.lax.Precision.HIGHEST
    gx = jnp.matmul(_q8(g, -1), _q8(w, 0).T, precision=hp)
    x2 = _q8(x, -1).reshape(-1, x.shape[-1])
    gw = jnp.matmul(x2.T, _q8(g.reshape(-1, g.shape[-1]), 0), precision=hp)
    return gx, gw


_qmatmul.defvjp(_qmatmul_fwd, _qmatmul_bwd)


def _mm(x, p, prec, quant=False):
    y = _qmatmul(x, p["w"]) if quant else jnp.matmul(x, p["w"], precision=prec)
    return y + p["b"] if "b" in p else y


def _layernorm(p, x):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-5) * p["scale"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def forward(params: Dict, x: Dict, w: Dict, *, prec=jax.lax.Precision.HIGHEST, quant: bool = False) -> Dict:
    """``x``: opcode (B, W) int, regbits/flags/brhist/memdist (B, W, F).
    Returns the head outputs at every position.  ``quant``: the training
    control -- every dense layer an fp8 matmul forward and backward and the
    opcode table rounded to fp8 (e4m3, scaled per output channel and per
    row); attention, norms and softmax in float32."""
    e = params["embed"]
    table = _q8(e["opcode"]["table"], -1) if quant else e["opcode"]["table"]

    def mm(v, layer):
        return _mm(v, layer, prec, quant)

    cats = [table[x["opcode"]], mm(x["regbits"], e["regbits"]), mm(x["flags"], e["flags"]),
            mm(x["brhist"], e["brhist"]), mm(x["memdist"], e["memdist"])]
    h = _gelu(mm(jnp.concatenate(cats, -1), e["combine"]))
    h = mm(h, params["adapt"])
    p = params["pred"]
    B, W, d = h.shape
    nh = w["n_heads"]
    hd = d // nh
    h = h + p["pos"][:W]
    causal = jnp.tril(jnp.ones((W, W), bool))
    for blk in p["blocks"]:
        qkv = mm(_layernorm(blk["ln1"], h), blk["qkv"]).reshape(B, W, 3, nh, hd)
        q, k, v = (qkv[:, :, i].transpose(0, 2, 1, 3) for i in range(3))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=prec) / math.sqrt(hd)
        a = jax.nn.softmax(jnp.where(causal, s, -1e30), -1)
        o = jnp.einsum("bhqk,bhkd->bhqd", a, v, precision=prec).transpose(0, 2, 1, 3).reshape(B, W, d)
        h = h + mm(o, blk["proj"])
        h = h + mm(_gelu(mm(_layernorm(blk["ln2"], h), blk["up"])), blk["down"])
    h = _layernorm(p["ln_f"], h)
    lat = mm(h, p["head_lat"])
    nb = len(LAT_EDGES)
    return {
        "fetch_logits": lat[..., :nb],
        "exec_logits": lat[..., nb:],
        "mispred_logit": mm(h, p["head_branch"])[..., 0],
        "dlevel_logits": mm(h, p["head_dlevel"]),
        "icache_logit": mm(h, p["head_icache"])[..., 0],
        "tlb_logit": mm(h, p["head_tlb"])[..., 0],
    }


# Logit tolerances at which ``simulate`` bounds each metric: 0, then
# geometric from 1e-4 to 1e2 (ratio ~1.12).
DELTAS = np.concatenate([[0.0], np.geomspace(1e-4, 1e2, 121)]).astype(np.float32)
REP2 = (2 * LAT_REPS).astype(np.int32)  # bucket representatives, doubled: integers


def simulate(params: Dict, trace: np.ndarray, w: Dict, *, block: int = 256, quant: bool = False,
             bounds: bool = True) -> Dict:
    """CPI, branch MPKI and L1D MPKI of one functional trace: the model over
    its non-overlapping windows, ``block`` windows per device call, decoded
    and folded on the host (exact integer sums).  CPI counts every
    instruction's fetch latency plus the last instruction's exec latency,
    over the windowed instructions.  A branch mispredicts where its logit
    is positive (sigmoid > 1/2); an L1D miss is a memory op whose most
    likely level is L2 or beyond.  ``quant``: the fp8 control (see
    ``forward``).

    ``bounds``: also, for every tolerance ``d`` of ``DELTAS``, the range
    each count can take when every decision may pick any choice whose logit
    lies at most ``d`` below the best one (``decision_gaps`` reads them):
    total cycles (doubled), mispredicted branches, L1D misses."""
    from . import features as F

    feats = F.features(trace, w)
    win = {k: F.windows(v, w["window"]) for k, v in feats.items()}
    is_br = F.windows(trace["is_branch"], w["window"]).astype(bool)
    is_mem = F.windows(trace["is_mem"], w["window"]).astype(bool)
    nw, W = win["opcode"].shape
    heads = _jit_heads(W, w["n_heads"], quant, bounds)
    fetch2 = mispred = l1d = 0
    lo_hi = np.zeros((3, 2, len(DELTAS)), np.int64)
    last = None
    for lo in range(0, nw, block):
        hi = min(lo + block, nw)
        x = {k: _pad(v[lo:hi], block) for k, v in win.items()}
        x["is_branch"] = _pad(is_br[lo:hi], block)
        x["is_mem"] = _pad(is_mem[lo:hi], block)
        out = jax.device_get(heads(params, x))
        fetch2 += int(out["fetch2"][: hi - lo].astype(np.int64).sum())
        mispred += int(out["mispred"][: hi - lo].astype(np.int64).sum())
        l1d += int(out["l1d"][: hi - lo].astype(np.int64).sum())
        if bounds:
            lo_hi += out["bounds"][: hi - lo].astype(np.int64).sum(0)
        last = out["exec_last"][hi - lo - 1]
    n = nw * W
    exec_last = float(LAT_REPS[int(np.argmax(last))])
    res = {"cpi": (fetch2 / 2.0 + exec_last) / n, "branch_mpki": 1000.0 * mispred / n,
           "l1d_mpki": 1000.0 * l1d / n, "num_instructions": n}
    if bounds:
        # the last instruction's exec latency joins the cycle count's range
        cand = last[None, :] >= last.max() - DELTAS[:, None]
        lo_hi[0, 0] += np.where(cand, REP2[None, :], 2**30).min(-1)
        lo_hi[0, 1] += np.where(cand, REP2[None, :], -1).max(-1)
        res["bounds"] = lo_hi
    return res


def decision_gaps(got: Dict, want: Dict) -> Dict[str, float]:
    """The least logit tolerance (of ``DELTAS``) at which the reference's
    range for each count holds what the program reported (``got``: its
    metrics): how far at least one of the program's decisions lies below
    the reference's best choice.  Past the largest tolerance: 1e3.

      fetch_logit_gap    latency buckets (total cycles, from CPI)
      branch_logit_gap   the mispredict logit against 0 (branch MPKI)
      dlevel_logit_gap   the best miss level against the best hit level
                         (L1D MPKI)

    Counts from MPKIs are rounded to whole instructions; total cycles are
    allowed the program's float32 rounding (4e-6 relative)."""
    n = want["num_instructions"]
    prog = [2.0 * got["cpi"] * n, got["branch_mpki"] * n / 1000.0, got["l1d_mpki"] * n / 1000.0]
    tol = [4e-6 * abs(prog[0]) + 1.0, 0.5, 0.5]
    out = {}
    for k, name in enumerate(("fetch_logit_gap", "branch_logit_gap", "dlevel_logit_gap")):
        lo, hi = want["bounds"][k]
        ok = (prog[k] >= lo - tol[k]) & (prog[k] <= hi + tol[k])
        out[name] = float(DELTAS[np.argmax(ok)]) if ok.any() else 1e3
    return out


def _pad(a: np.ndarray, rows: int) -> np.ndarray:
    if len(a) == rows:
        return a
    out = np.zeros((rows,) + a.shape[1:], a.dtype)
    out[: len(a)] = a
    return out


_JITS: Dict = {}


def _jit_heads(W: int, n_heads: int, quant: bool, bounds: bool):
    """Per window: the decoded counts (doubled fetch cycles, mispredicts,
    L1D misses), the last position's exec logits and, with ``bounds``, the
    (lo, hi) of each count at every tolerance of ``DELTAS``."""
    key = (W, n_heads, quant, bounds)
    if key in _JITS:
        return _JITS[key]
    w = {"n_heads": n_heads}
    rep2 = jnp.asarray(REP2)
    deltas = jnp.asarray(DELTAS)

    def heads(p, x):
        out = forward(p, x, w, quant=quant)
        f = out["fetch_logits"]
        z = out["mispred_logit"]
        dl = out["dlevel_logits"]
        g = dl[..., DLEVEL_L2:].max(-1) - dl[..., :DLEVEL_L2].max(-1)
        br, mem = x["is_branch"], x["is_mem"]
        res = {
            "fetch2": rep2[jnp.argmax(f, -1)].sum(-1),
            "mispred": ((z > 0) & br).sum(-1),
            "l1d": ((jnp.argmax(dl, -1) >= DLEVEL_L2) & mem).sum(-1),
            "exec_last": out["exec_logits"][:, -1],
        }
        if bounds:
            def per_delta(d):
                cand = f >= (f.max(-1, keepdims=True) - d)
                fetch = [jnp.where(cand, rep2, 2**30).min(-1).sum(-1),
                         jnp.where(cand, rep2, -1).max(-1).sum(-1)]
                branch = [((z > d) & br).sum(-1), ((z > -d) & br).sum(-1)]
                level = [((g > d) & mem).sum(-1), ((g > -d) & mem).sum(-1)]
                return jnp.stack([jnp.stack(fetch), jnp.stack(branch), jnp.stack(level)])

            # (deltas, 3, 2, windows) -> (windows, 3, 2, deltas)
            res["bounds"] = jax.lax.map(per_delta, deltas).transpose(3, 1, 2, 0)
        return res

    _JITS[key] = jax.jit(heads)
    return _JITS[key]


# ---------------------------------------------------------------------------
# training: loss, gradient, AdamW
# ---------------------------------------------------------------------------


def _ce(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    return -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]


def _bce(logit, target, mask):
    per = jnp.maximum(logit, 0) - logit * target + jnp.log1p(jnp.exp(-jnp.abs(logit)))
    return (per * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def loss(params: Dict, batch: Dict, w: Dict, *, prec=jax.lax.Precision.HIGHEST, quant: bool = False):
    """The multi-metric loss: cross-entropy over latency buckets, masked
    binary cross-entropy for mispredicts (branches), icache (all) and TLB
    (memory ops), cross-entropy of the access level (memory ops)."""
    out = forward(params, batch, w, prec=prec, quant=quant)
    lab = batch["labels"]
    edges = jnp.asarray(LAT_EDGES)

    def bucket(x):
        return jnp.clip(jnp.searchsorted(edges, x, side="right") - 1, 0, len(LAT_EDGES) - 1)

    mem = lab["is_mem"]
    parts = {
        "fetch_lat": _ce(out["fetch_logits"], bucket(lab["fetch_lat"])).mean(),
        "exec_lat": _ce(out["exec_logits"], bucket(lab["exec_lat"])).mean(),
        "mispred": _bce(out["mispred_logit"], lab["mispred"], lab["is_branch"]),
        "dlevel": (_ce(out["dlevel_logits"], lab["dlevel"]) * mem).sum() / jnp.maximum(mem.sum(), 1.0),
        "icache_miss": _bce(out["icache_logit"], lab["icache_miss"], jnp.ones_like(mem)),
        "tlb_miss": _bce(out["tlb_logit"], lab["tlb_miss"], mem),
    }
    return sum(LOSS_WEIGHTS[k] * v for k, v in parts.items())


def head_grad(params: Dict, batch: Dict, w: Dict, **kw):
    """Loss and gradient of the per-µarch groups (``adapt``, ``pred``), the
    embedding held fixed (§4.3 transfer)."""
    def f(head):
        return loss({"embed": params["embed"], **head}, batch, w, **kw)

    head = {"adapt": params["adapt"], "pred": params["pred"]}
    return jax.value_and_grad(f)(head)


def clip(grads, max_norm: float = 1.0):
    """Global-norm clipping: the gradient the optimizer consumes."""
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    return jax.tree.map(lambda g: g * jnp.minimum(1.0, max_norm / (norm + 1e-9)), grads)


def adamw(params, grads, state, *, lr, b1=0.9, b2=0.999, eps=1e-8):
    """AdamW without weight decay (the recipe's setting), bias-corrected;
    ``state`` is (step, m, v)."""
    t, m, v = state
    t = t + 1
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    new = jax.tree.map(lambda p, a, b: p - lr * (a / c1) / (jnp.sqrt(b / c2) + eps), params, m, v)
    return new, (t, m, v)
