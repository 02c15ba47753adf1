"""Bring-up smoke: the simulator's main path on a TPU, at the published widths.

One process, through the entry points a user calls (``repro.api.Session``,
``StreamingEngine``, ``TraceServer``), at the widths of
``repro.configs.tao`` (W=129, d_model=512, 8 heads, 6 layers, d_ff=2048,
d_cat=128, N_b=1024, N_q=32, N_m=64), fp32, ``feature_backend="fused"``:

  capture    functional traces: ``dee`` (training) and the unseen ``mcf``
  train      a few steps of ``Session.train`` on detailed-sim labels
  simulate   the unseen trace through the fused megakernel + jitted step
  features   fused device features vs NumPy ``extract_features``
  reference  the same params on the host: NumPy features + fp32
             ``tao_forward`` on the CPU device of this process
  serve      8 requests from two tenants through an in-process TraceServer,
             whose NumPy feature extraction runs on worker threads on an
             accelerator (the path the CPU never takes)

``--chips 4`` runs only the sharded path and what it is compared with:
``StreamingEngine.simulate`` under a 4-device data ``ExecutionPlan`` and a
data-sharded ``Session.sweep`` of 2 models x 2 traces, each against the
single-device run of the same inputs.

Every phase prints its wall seconds and XLA compile count.  Any failed
phase or comparison makes the exit code non-zero.  The last line of a
passing run on a TPU is ``{"ok": true, "device": {...}}``; the script never
prints it anywhere else.  Without a TPU it exits 2 at once, except as a
rehearsal: ``JAX_PLATFORMS=cpu python chip_smoke.py --tiny`` (add
``--chips 4`` with ``XLA_FLAGS=--xla_force_host_platform_device_count=4``)
runs every phase at a tiny geometry, Pallas in interpret mode, and exits 3
with no result line.

Usage:
    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the sharded path, on a 4-chip host
"""
from __future__ import annotations

import argparse
import asyncio
import contextlib
import json
import math
import os
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# Chip vs host reference, and sharded vs single-device.  The TPU runs fp32
# matmuls at the default precision (bf16 passes, f32 accumulation) and its
# own f32 divide, so the chip's per-instruction predictions differ from the
# CPU's in low-order bits.  CPI averages ~10^5 latency predictions, so that
# noise shrinks to well under a percent; the MPKIs count thresholded
# decisions (sigmoid > 0.5, argmax level), which the noise can flip one at a
# time near a boundary, so they get a relative band with an absolute floor.
CPI_REL_BAND = 0.01
MPKI_REL_BAND = 0.05
MPKI_ABS_BAND = 1.0
# signed-log memory-distance features lie in [-1, 1]; the chip's eager f32
# ops round a few ulp away from NumPy's at most
MEMDIST_ATOL = 1e-5

RESULT_METRICS = ("cpi", "branch_mpki", "l1d_mpki")


def _geometry(tiny: bool):
    from repro.configs.tao import CONFIG
    from repro.core import FeatureConfig, TaoConfig

    if not tiny:
        # trace lengths: ~24 train steps per epoch; 12 batches of 64 x 129
        # to simulate
        return CONFIG, 64, 50_000, 100_000
    cfg = TaoConfig(
        window=17, d_model=32, n_heads=2, n_layers=1, d_ff=64, d_cat=16,
        features=FeatureConfig(n_buckets=32, n_queue=4, n_mem=8),
    )
    return cfg, 16, 4_000, 6_000


class Smoke:
    """Phase bookkeeping: wall time, XLA compiles, and failures."""

    def __init__(self):
        import jax

        self.failures = []
        self._compiles = 0

        def listen(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self._compiles += 1

        jax.monitoring.register_event_duration_secs_listener(listen)

    @contextlib.contextmanager
    def phase(self, name: str):
        print(f"== {name}", flush=True)
        t0, c0 = time.perf_counter(), self._compiles
        try:
            yield
        except Exception:
            traceback.print_exc()
            self.failures.append(name)
            print(f"!! {name} FAILED", flush=True)
        finally:
            print(
                f"   [{name}] {time.perf_counter() - t0:.3f} s, "
                f"{self._compiles - c0} XLA compiles",
                flush=True,
            )

    def check(self, ok: bool, what: str) -> None:
        print(f"   {'ok  ' if ok else 'FAIL'} {what}", flush=True)
        if not ok:
            raise AssertionError(what)


def within_band(got: dict, ref: dict) -> list:
    """The metrics of ``got`` outside the band around ``ref``."""
    bad = []
    for m in RESULT_METRICS:
        a, b = float(got[m]), float(ref[m])
        if m == "cpi":
            ok = abs(a - b) <= CPI_REL_BAND * abs(b)
        else:
            ok = abs(a - b) <= max(MPKI_REL_BAND * abs(b), MPKI_ABS_BAND)
        if not (ok and math.isfinite(a)):
            bad.append(m)
    return bad


def metrics_of(r) -> dict:
    return {m: float(r.metrics[m]) for m in RESULT_METRICS}


def diff_line(got: dict, ref: dict) -> str:
    return ", ".join(
        f"{m}: {got[m]!r} vs {ref[m]!r} (diff {got[m] - ref[m]:+.6g}"
        + (f", rel {(got[m] - ref[m]) / ref[m]:+.3g})" if ref[m] else ")")
        for m in RESULT_METRICS
    )


def numpy_memdist_raw(trace, n_mem: int) -> np.ndarray:
    """Raw (pre-signed-log) memory-distance deltas, the lag-k formulation of
    ``core.features._memory_distance`` without the compression."""
    out = np.zeros((len(trace), n_mem), np.float32)
    idx = np.nonzero(trace["is_mem"])[0]
    addrs = trace["addr"][idx].astype(np.int64)
    for k in range(min(n_mem, max(len(idx) - 1, 0))):
        out[idx[k + 1:], k] = (addrs[k + 1:] - addrs[: len(idx) - 1 - k]).astype(np.float32)
    return out


def run_one_chip(smoke: Smoke, args) -> None:
    import jax
    import jax.numpy as jnp

    from repro.api import ModelRegistry, ServeRequest, Session, TraceServer
    from repro.compat import on_tpu
    from repro.core.features import extract_features
    from repro.core.simulate import simulate_trace_legacy
    from repro.engine import persistent_cache_status
    from repro.kernels.fused.ops import (
        FusedExtractor,
        init_fused_state,
        signed_log_device,
        trace_columns,
    )
    from repro.train.trainer import train_step_compiles
    from repro.uarch import UARCH_A

    cfg, batch, n_train, n_sim = _geometry(args.tiny)
    fc = cfg.features
    st = {}

    with smoke.phase("setup"):
        sess = Session(
            cfg, batch_size=batch, feature_backend="fused", seed=args.seed,
            compile_cache=True,
        )
        print(
            f"   widths: W={cfg.window} d_model={cfg.d_model} heads={cfg.n_heads} "
            f"layers={cfg.n_layers} d_ff={cfg.d_ff} d_cat={cfg.d_cat} "
            f"N_b={fc.n_buckets} N_q={fc.n_queue} N_m={fc.n_mem} "
            f"batch={batch}x{cfg.window} dtype={cfg.dtype}"
        )
        print(f"   compile cache: {persistent_cache_status()['dir']}")
        print(f"   Pallas lowering: {'Mosaic (native)' if on_tpu() else 'interpret'}")

    with smoke.phase("capture"):
        st["train_tr"] = sess.capture("dee", n_train)
        st["sim_tr"] = sess.capture("mcf", n_sim)
        print(f"   dee:{n_train} (train), mcf:{n_sim} (unseen, simulated)")

    with smoke.phase("train"):
        c0 = train_step_compiles()
        model = sess.train(
            UARCH_A, [st["train_tr"]], epochs=2, batch_size=16, name="dee"
        )
        losses = [float(x) for x in model.losses]
        print(f"   steps={model.steps} train-step traces={train_step_compiles() - c0}")
        print(f"   losses: first={losses[0]!r} last={losses[-1]!r} all={losses}")
        smoke.check(model.steps > 0 and all(map(math.isfinite, losses)),
                    "training losses finite")
        st["model"] = model
    # a failed train phase fails the run, but the later phases still run
    model = st.get("model") or sess.init_model(seed=args.seed, name="dee")
    sim_tr = st["sim_tr"]
    ft = sim_tr.functional

    with smoke.phase("simulate (fused)"):
        engine = model.engine(batch_size=batch, feature_backend="fused")
        t0 = time.perf_counter()
        engine.warmup(len(ft))
        setup_s = time.perf_counter() - t0
        r = engine.simulate(ft)
        st["chip"] = metrics_of(r)
        print(f"   set-up (step compile) {setup_s:.3f} s; step traces={engine.num_compiles}")
        print(f"   n={r.num_instructions} " + " ".join(
            f"{k}={v!r}" for k, v in st["chip"].items()))
        print(f"   simulate wall {r.seconds:.3f} s (one cold-column run; not a benchmark)")
        smoke.check(engine.num_compiles == 1, "one step compile for the geometry")
        smoke.check(all(map(math.isfinite, st["chip"].values())), "metrics finite")
        if on_tpu():
            # the compiled extraction program the engine's fused batches go through
            from repro.kernels.fused.ops import _fused_padded, _pack

            per = batch * cfg.window
            cols = trace_columns(ft[:per], fc)
            state = init_fused_state(fc)
            hlo = _fused_padded.lower(
                _pack(cols, 0, per), state["table"], state["queue"],
                np.array([per, 0], np.int32), shape=(batch, cfg.window),
                n_queue=fc.n_queue, n_mem=fc.n_mem, n_flags=fc.flags_dim,
                chunk=512, interpret=False,
            ).as_text()
            smoke.check("tpu_custom_call" in hlo,
                        "fused megakernel lowers to a Mosaic tpu_custom_call")

    with smoke.phase("features: fused device vs NumPy"):
        cols = trace_columns(ft, fc)
        dev = FusedExtractor(cols, fc).next_batch(len(ft))
        ref = extract_features(ft, fc, with_labels=False)
        for k in ("opcode", "regbits", "flags", "brhist"):
            smoke.check(np.array_equal(np.asarray(dev[k]), getattr(ref, k)),
                        f"{k} bit-exact")
        raw = numpy_memdist_raw(ft, fc.n_mem)
        md = np.asarray(dev["memdist"])
        compiled = jax.jit(signed_log_device)(jnp.asarray(raw), np.int32(0))
        smoke.check(
            np.array_equal(md.view(np.int32), np.asarray(compiled).view(np.int32)),
            "fused memdist == this device's compiled signed_log of the exact raw deltas",
        )
        err = np.abs(md - ref.memdist)
        print(f"   memdist vs NumPy: max |diff| {err.max()!r}, "
              f"{int((err > 0).sum())} of {err.size} values differ")
        smoke.check(float(err.max()) <= MEMDIST_ATOL, f"memdist within {MEMDIST_ATOL}")

    with smoke.phase("reference: NumPy features + fp32 tao_forward on host CPU"):
        cpu = jax.devices("cpu")[0]
        with jax.default_device(cpu):
            host = simulate_trace_legacy(
                jax.device_put(model.params, cpu), ft, cfg, batch_size=batch,
                features=extract_features(ft, fc, with_labels=False),
            )
        ref_m = metrics_of(host)
        print(f"   host n={host.num_instructions}")
        print(f"   chip vs host: {diff_line(st['chip'], ref_m)}")
        bad = within_band(st["chip"], ref_m)
        smoke.check(not bad, f"chip within band of host reference (outside: {bad})")

    with smoke.phase("serve: 8 requests, 2 tenants, TraceServer"):
        reg = ModelRegistry()
        reg.register("dee", model)
        traces = [sim_tr, st["train_tr"]]

        async def serve():
            server = TraceServer(reg, batch_size=batch)
            async with server:
                server.warmup([len(t) for t in traces])
                futs = [
                    server.submit(ServeRequest(model="dee", trace=tr, tenant=tenant))
                    for tenant in ("alice", "bob")
                    for tr in traces + traces
                ]
                return await asyncio.gather(*futs), server

        out, server = asyncio.run(serve())
        print(f"   threaded host extraction: {server.extract_async}; "
              f"request-attributed step compiles: {server.num_compiles}")
        smoke.check(server.extract_async == on_tpu(),
                    "extraction threads exactly when an accelerator runs the step")
        direct = {id(tr): model.simulate(tr, feature_backend="numpy") for tr in traces}
        want = [direct[id(tr)] for _ in ("alice", "bob") for tr in traces + traces]
        same = all(o.metrics["cpi"] == w.cpi for o, w in zip(out, want))
        print(f"   answered {len(out)} of 8: " + ", ".join(
            f"{o.tenant}/{o.num_instructions}:cpi={o.metrics['cpi']:.6g}" for o in out))
        smoke.check(len(out) == 8 and same,
                    "every request answered, equal to a direct simulate")


def run_four_chips(smoke: Smoke, args) -> None:
    import jax

    from repro.api import Session
    from repro.compat import make_mesh
    from repro.core.dataset import stream_batches
    from repro.core.features import extract_features
    from repro.engine import ExecutionPlan, clear_step_cache

    cfg, batch, _, n_sim = _geometry(args.tiny)
    st = {}

    with smoke.phase("setup: 4-device data plan"):
        devs = jax.devices()
        smoke.check(len(devs) >= 4, f"4 devices visible (found {len(devs)})")
        mesh = make_mesh((4,), ("data",))
        plan = ExecutionPlan.resolve(mesh, batch_size=batch)
        print(f"   plan {plan.describe()}")
        sess = Session(cfg, batch_size=batch, feature_backend="fused",
                       seed=args.seed, compile_cache=True)
        traces = {"mcf": sess.capture("mcf", n_sim),
                  "lee": sess.capture("lee", n_sim // 2)}
        models = {f"m{i}": sess.init_model(seed=args.seed + i, name=f"m{i}")
                  for i in range(2)}
        st.update(mesh=mesh, plan=plan)

    with smoke.phase("single-device reference (device 0)"):
        single = {
            f"{mn}/{tn}": metrics_of(m.simulate(tr))
            for mn, m in models.items() for tn, tr in traces.items()
        }
        for k, v in single.items():
            print(f"   {k}: {v}")
        st["single"] = single

    with smoke.phase("placement: a batch spreads over the 4 devices"):
        fs = extract_features(traces["mcf"].functional, cfg.features, with_labels=False)
        host = next(stream_batches(fs, cfg.window, batch, stride=cfg.window))
        placed = plan.device_put(host)
        shards = placed["valid"].addressable_shards
        print("   " + ", ".join(f"{s.device}: rows {s.data.shape[0]}" for s in shards))
        smoke.check(
            len({s.device for s in shards}) == 4
            and all(s.data.shape[0] == batch // 4 for s in shards),
            "4 distinct devices, batch/4 rows each",
        )

    with smoke.phase("StreamingEngine.simulate under the data plan"):
        clear_step_cache()
        m0 = models["m0"]
        engine = m0.engine(batch_size=batch, feature_backend="fused", plan=plan)
        for tn, tr in traces.items():
            got = metrics_of(engine.simulate(tr.functional))
            ref = st["single"][f"m0/{tn}"]
            print(f"   m0/{tn} sharded vs single: {diff_line(got, ref)}")
            bad = within_band(got, ref)
            smoke.check(not bad, f"m0/{tn} within band (outside: {bad})")
        print(f"   step traces: {engine.num_compiles}")
        smoke.check(engine.num_compiles == 1, "one compile for the geometry")
        for d in devs[:4]:
            ms = d.memory_stats() or {}
            print(f"   {d}: peak_bytes_in_use={ms.get('peak_bytes_in_use')}")

    with smoke.phase("Session.sweep: 2 models x 2 traces, data-sharded"):
        clear_step_cache()
        rep = sess.sweep(models, traces, plan=plan)
        print(f"   plan_kind={rep.plan_kind} num_compiles={rep.num_compiles} "
              f"jobs={len(rep.results)}")
        for k, r in sorted(rep.results.items()):
            got = metrics_of(r)
            print(f"   {k} sweep vs single: {diff_line(got, st['single'][k])}")
            bad = within_band(got, st["single"][k])
            smoke.check(not bad, f"{k} within band (outside: {bad})")
        smoke.check(rep.plan_kind == "sharded" and rep.num_compiles == 1,
                    "sharded sweep, one compile for its one geometry")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded path, on a 4-chip host")
    ap.add_argument("--tiny", action="store_true",
                    help="rehearsal geometry; never prints a result line")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    dev = devs[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} count={len(devs)}",
          flush=True)
    on_chip = dev.platform == "tpu"
    if not on_chip and not args.tiny:
        print("no TPU found; rehearse on the CPU with --tiny", file=sys.stderr)
        return 2

    smoke = Smoke()
    t0 = time.perf_counter()
    (run_four_chips if args.chips == 4 else run_one_chip)(smoke, args)
    print(f"total {time.perf_counter() - t0:.3f} s; failed phases: {smoke.failures}",
          flush=True)
    if smoke.failures:
        return 1
    if not on_chip or args.tiny:
        print("rehearsal passed; no TPU result", flush=True)
        return 3
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devs)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
