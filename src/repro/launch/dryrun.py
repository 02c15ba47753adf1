"""Multi-pod dry-run: AOT lower + compile every (architecture × input shape ×
mesh) cell and extract the roofline terms.

For each cell this lowers the appropriate step function with
ShapeDtypeStruct stand-ins (no allocation):

  train_4k     -> train_step   (fwd+bwd+AdamW, microbatched)
  prefill_32k  -> prefill      (full-prompt forward, returns cache)
                  (hubert: encode — encoder-only has no cache)
  decode_32k   -> decode_step  (one token over a 32k cache)
  long_500k    -> decode_step  (SSM / hybrid state decode at 524288 context)

and records memory_analysis(), cost_analysis(), and the collective-op
inventory parsed from the compiled HLO into a JSON results file
(resumable: completed cells are skipped).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --mesh single --out results.json
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k
"""
# The placeholder-device flag must be set before ANY other import triggers
# jax initialization (jax locks the device count on first init).
import os  # noqa: E402  isort: skip

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
import time
import traceback
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..compat import NamedSharding, PartitionSpec
from ..configs import ARCH_IDS, get_arch
from ..data.pipeline import make_lm_batch_specs
from ..distributed.sharding import logical_to_spec, mesh_context, tree_shardings
from ..models.backbone import Model
from ..train.trainer import TrainConfig, batch_axes, init_state, make_train_step, state_axes
from .hloanalysis import analyze_hlo
from .mesh import make_production_mesh
from .roofline import analytic_flops, analytic_hbm_bytes

# ---------------------------------------------------------------------------
# cell definitions
# ---------------------------------------------------------------------------

SHAPES = {
    "train_4k": dict(kind="train", seq=4096, batch=256),
    "prefill_32k": dict(kind="prefill", seq=32768, batch=32),
    "decode_32k": dict(kind="decode", seq=32768, batch=128),
    "long_500k": dict(kind="decode", seq=524288, batch=1),
}

# v5e constants for the roofline (per chip)
PEAK_FLOPS = 197e12      # bf16
HBM_BW = 819e9           # bytes/s
ICI_BW = 50e9            # bytes/s/link


def cell_supported(arch: str, shape: str) -> Tuple[bool, str]:
    cfg = get_arch(arch)
    if cfg.encoder_only and shape in ("decode_32k", "long_500k"):
        return False, "encoder-only: no decode step"
    if shape == "long_500k" and not cfg.sub_quadratic:
        return False, "pure full-attention arch: long_500k skipped (DESIGN.md)"
    return True, ""


def runnable_cells():
    for arch in ARCH_IDS:
        for shape in SHAPES:
            ok, why = cell_supported(arch, shape)
            if ok:
                yield arch, shape


# ---------------------------------------------------------------------------
# lowering
# ---------------------------------------------------------------------------


# kept under its historical name; the implementation is the shared
# resolver in distributed.sharding (also behind trainer.state_shardings)
_shardings_for = tree_shardings


def lower_cell(arch: str, shape: str, mesh, *, microbatches: int = 0,
               extra_cfg: Optional[Dict] = None):
    """Returns (lowered, meta) for one cell."""
    import dataclasses

    spec = SHAPES[shape]
    cfg = get_arch(arch)
    if extra_cfg:
        cfg = dataclasses.replace(cfg, **extra_cfg)
    kind = spec["kind"]
    B, S = spec["batch"], spec["seq"]
    # (chunked prefill was evaluated for the MoE cells and REFUTED: the
    # cache re-layout copy costs more than the dispatch temps it saves —
    # see EXPERIMENTS.md §Perf.  cfg.prefill_chunks stays available for
    # bandwidth-constrained serving hosts.)
    model = Model(cfg)

    if kind == "train" and microbatches == 0:
        # auto: keep the saved per-layer residuals (B_local/µb × S × d × 2B
        # × n_layers under full remat) near ~2 GiB/device
        data_ways = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
        b_loc = max(1, B // data_ways)
        resid = cfg.n_layers * b_loc * S * cfg.d_model * 2
        microbatches = 1
        while resid / microbatches > 2 * 1024**3 and microbatches < b_loc:
            microbatches *= 2
    elif microbatches == 0:
        microbatches = 1

    with mesh_context(mesh):
        if kind == "train":
            tcfg = TrainConfig(microbatches=microbatches)
            step = make_train_step(model, tcfg)
            state_sds = jax.eval_shape(
                lambda k: init_state(model, k, tcfg), jax.random.PRNGKey(0)
            )
            s_axes = state_axes(model)
            state_sh = _shardings_for(s_axes, state_sds, mesh)
            batch_sds = make_lm_batch_specs(cfg, B, S)
            b_axes = batch_axes(model)
            batch_sh = _shardings_for(
                {k: tuple(v) for k, v in b_axes.items()}, batch_sds, mesh
            )
            fn = jax.jit(
                step,
                in_shardings=(state_sh, batch_sh),
                out_shardings=(state_sh, None),
                donate_argnums=(0,),
            )
            lowered = fn.lower(state_sds, batch_sds)
            n_params = sum(
                int(np.prod(x.shape)) for x in jax.tree.leaves(state_sds.params)
            )
        elif kind == "prefill":
            params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
            p_axes = model.param_axes()
            params_sh = _shardings_for(p_axes, params_sds, mesh)
            batch_sds = make_lm_batch_specs(cfg, B, S)
            batch_sds.pop("labels")
            b_axes = {k: tuple(v) for k, v in batch_axes(model).items() if k != "labels"}
            batch_sh = _shardings_for(b_axes, batch_sds, mesh)
            fwd = model.encode if cfg.encoder_only else model.prefill
            fn = jax.jit(fwd, in_shardings=(params_sh, batch_sh))
            lowered = fn.lower(params_sds, batch_sds)
            n_params = sum(
                int(np.prod(x.shape)) for x in jax.tree.leaves(params_sds)
            )
        else:  # decode
            params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
            p_axes = model.param_axes()
            params_sh = _shardings_for(p_axes, params_sds, mesh)
            cache_sds = jax.eval_shape(lambda: model.init_cache(B, S))
            c_axes = model.cache_axes()
            cache_sh = _shardings_for(c_axes, cache_sds, mesh)
            tok_sds = jax.ShapeDtypeStruct((B,), jnp.int32)
            pos_sds = jax.ShapeDtypeStruct((), jnp.int32)
            rep = NamedSharding(mesh, PartitionSpec())
            tok_sh = NamedSharding(
                mesh,
                logical_to_spec(("batch",), shape=(B,), mesh=mesh),
            )
            fn = jax.jit(
                model.decode_step,
                in_shardings=(params_sh, cache_sh, tok_sh, rep),
                out_shardings=(None, cache_sh),
                donate_argnums=(1,),
            )
            lowered = fn.lower(params_sds, cache_sds, tok_sds, pos_sds)
            n_params = sum(
                int(np.prod(x.shape)) for x in jax.tree.leaves(params_sds)
            )

    meta = {"arch": arch, "shape": shape, "kind": kind, "batch": B, "seq": S,
            "n_params": n_params, "microbatches": microbatches}
    if kind == "decode":
        meta["cache_bytes"] = int(
            sum(
                int(np.prod(x.shape)) * x.dtype.itemsize
                for x in jax.tree.leaves(cache_sds)
            )
        )
    return lowered, meta, cfg


# ---------------------------------------------------------------------------
# analysis
# ---------------------------------------------------------------------------

def model_flops(cfg, meta) -> float:
    """6·N·D (train) / 2·N·D (inference) with N_active for MoE.

    N excludes the input embedding table when it is untied (a gather, not a
    matmul); tied tables participate in the logits matmul and stay counted.
    """
    n = meta["n_params"]
    if not cfg.tie_embeddings:
        n -= cfg.vocab * cfg.d_model
    if cfg.moe is not None:
        m = cfg.moe
        n_moe_layers = cfg.n_layers - m.first_dense_layers
        routed = 3 * cfg.d_model * m.d_ff_expert * m.num_experts * n_moe_layers
        active = routed * (m.top_k / m.num_experts)
        n = n - routed + active
    if meta["kind"] == "train":
        tokens = meta["batch"] * meta["seq"]
        return 6.0 * n * tokens
    if meta["kind"] == "prefill":
        tokens = meta["batch"] * meta["seq"]
        return 2.0 * n * tokens
    return 2.0 * n * meta["batch"]  # decode: one token per sequence


def analyze(lowered, compiled, meta, cfg, mesh) -> Dict:
    n_dev = mesh.devices.size
    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    h = analyze_hlo(hlo)  # loop-aware dot flops + collective bytes (per device)

    # FLOPs: loop-aware HLO dot count (per-device, post-SPMD).  The raw
    # cost_analysis value is recorded too — on scanned graphs it counts each
    # while body once (see hloanalysis.py docstring).
    flops_dev_hlo = float(h["dot_flops"])
    flops_global_analytic = analytic_flops(cfg, meta)
    flops_dev = max(flops_dev_hlo, flops_global_analytic / n_dev)

    cache_bytes = int(meta.get("cache_bytes", 0))
    bytes_global = analytic_hbm_bytes(cfg, meta, meta["n_params"], cache_bytes)
    bytes_dev = bytes_global / n_dev

    wire = float(h["collective_wire_bytes"])
    compute_s = flops_dev / PEAK_FLOPS
    memory_s = bytes_dev / HBM_BW
    collective_s = wire / ICI_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s, "collective_s": collective_s}
    dominant = max(terms, key=terms.get)
    step_s = max(terms.values())
    mf = model_flops(cfg, meta)
    useful_ratio = mf / (flops_dev * n_dev) if flops_dev else 0.0
    roofline_frac = (mf / n_dev / step_s) / PEAK_FLOPS if step_s > 0 else 0.0
    per_dev_hbm = (
        mem.argument_size_in_bytes
        + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
        - mem.alias_size_in_bytes
    )
    # XLA:CPU legalizes bf16 elementwise/dynamic-update-slice ops through
    # f32 converts (verified in the HLO: convert->dus f32->convert around
    # the donated KV cache), inflating temp_size by ~2x cache for decode
    # cells.  TPU executes these natively in bf16 with in-place donation,
    # so we also record an analytic TPU-resident estimate for decode:
    # params + cache (donated/aliased) + 1 GiB working-set slack.
    pdt = 2 if cfg.param_dtype == "bfloat16" else 4
    if meta["kind"] == "decode":
        tpu_estimate = (
            meta["n_params"] * pdt + meta.get("cache_bytes", 0)
        ) / n_dev + 1 * 1024**3
    elif meta["kind"] == "train":
        # params (bf16) + Adam m (bf16) + v (f32) + f32 grads, all sharded
        # 256-way, + saved per-layer residuals (batch/µb × seq/SP × d) +
        # slack.  The gap vs memory_analysis is donation aliasing that the
        # CPU backend only partially performs (verified on a reduced case).
        msize = mesh.shape.get("model", 1)
        dsize = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
        mb = max(1, meta.get("microbatches", 1))
        b_loc = max(1, meta["batch"] // dsize // mb)
        resid = cfg.n_layers * b_loc * (meta["seq"] // msize) * cfg.d_model * 2
        tpu_estimate = (
            meta["n_params"] * (pdt + 2 + 4 + 4) / n_dev + resid + 1 * 1024**3
        )
    elif meta["kind"] == "prefill" and cfg.moe is not None:
        # MoE prefill temps are dominated by (Tg*k, d) slot-staging buffers
        # that XLA:CPU legalizes to f32 (verified in the HLO dump: paired
        # convert->scatter/gather f32 around every bf16 staging tensor).
        # TPU keeps them bf16 -> halve the temp estimate.
        tpu_estimate = (
            mem.argument_size_in_bytes
            + mem.output_size_in_bytes
            + mem.temp_size_in_bytes / 2
        )
    else:
        tpu_estimate = per_dev_hbm
    return {
        **meta,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "n_devices": int(n_dev),
        "flops_per_device": flops_dev,
        "flops_per_device_hlo": flops_dev_hlo,
        "flops_per_device_analytic": flops_global_analytic / n_dev,
        "flops_per_device_xla_costanalysis": float(ca.get("flops", 0.0)),
        "bytes_per_device": bytes_dev,
        "bytes_per_device_xla_costanalysis": float(ca.get("bytes accessed", 0.0)),
        "collectives": h["collectives"],
        "collective_bytes_per_device": float(h["collective_bytes"]),
        "collective_wire_bytes": wire,
        "memory": {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
            "per_device_total": int(per_dev_hbm),
            "tpu_estimate": int(tpu_estimate),
            "fits_16gb": bool(min(per_dev_hbm, tpu_estimate) <= 16 * 1024**3),
        },
        "roofline": {
            **terms,
            "dominant": dominant,
            "step_time_s": step_s,
            "model_flops": mf,
            "useful_flops_ratio": useful_ratio,
            "roofline_fraction": roofline_frac,
        },
    }


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def run_cell(arch: str, shape: str, multi_pod: bool, microbatches: int = 0,
             extra_cfg: Optional[Dict] = None) -> Dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    lowered, meta, cfg = lower_cell(
        arch, shape, mesh, microbatches=microbatches, extra_cfg=extra_cfg
    )
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()
    rec = analyze(lowered, compiled, meta, cfg, mesh)
    rec["lower_s"] = t1 - t0
    rec["compile_s"] = t2 - t1
    rec["multi_pod"] = multi_pod
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--microbatches", type=int, default=0)
    ap.add_argument("--out", default="dryrun_results.json")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    results = {}
    if os.path.exists(args.out):
        # always load: --force only re-runs the SELECTED cells (it must
        # never clobber the rest of the results file)
        with open(args.out) as f:
            results = json.load(f)

    cells = list(runnable_cells())
    if args.arch:
        cells = [(a, s) for a, s in cells if a == args.arch]
    if args.shape:
        cells = [(a, s) for a, s in cells if s == args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    for arch, shape in cells:
        for mp in meshes:
            key = f"{arch}|{shape}|{'multi' if mp else 'single'}"
            if key in results and not args.force:
                print(f"[skip] {key}")
                continue
            print(f"[run ] {key} ...", flush=True)
            try:
                rec = run_cell(arch, shape, mp, microbatches=args.microbatches)
                results[key] = rec
                r = rec["roofline"]
                print(
                    f"       ok: dominant={r['dominant']} step={r['step_time_s']:.4f}s "
                    f"roofline={r['roofline_fraction']*100:.1f}% "
                    f"mem/dev={rec['memory']['per_device_total']/2**30:.2f}GiB "
                    f"(lower {rec['lower_s']:.0f}s compile {rec['compile_s']:.0f}s)",
                    flush=True,
                )
            except Exception as e:
                results[key] = {"error": f"{type(e).__name__}: {e}"}
                print(f"       FAILED: {type(e).__name__}: {e}", flush=True)
                traceback.print_exc()
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)

    n_ok = sum(1 for v in results.values() if "error" not in v)
    n_bad = sum(1 for v in results.values() if "error" in v)
    print(f"\ndone: {n_ok} ok, {n_bad} failed -> {args.out}")


if __name__ == "__main__":
    main()
