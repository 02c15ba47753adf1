"""Table 4 + Fig. 10 — end-to-end time decomposition and trace economics.

  * functional vs detailed trace generation throughput (Fig 10b; paper: ~25x)
  * squashed/nop composition of the detailed-trace surplus (Fig 10a)
  * simulation (inference) throughput: streaming engine vs the pre-refactor
    host batch loop (`simulate_trace_legacy`), with the engine's compile
    count asserted to be exactly one
  * §4.2 feature-extraction throughput: host NumPy (`extract_features`),
    and the fused engine's speedup over the host pre-pass engine
  * the trace->logits megakernel (`feature_backend="fused"`, asserted
    bit-identical to the NumPy engine) and the int8 W8A8 engine, each with
    end-to-end MIPS and host->device bytes/instr (the committed baseline
    `benchmarks/baselines/BENCH_timing.json` + `check_regression` gate
    these rows in CI)
  * the Table-4 ratio: (trace gen + train + simulate) Tao vs SimNet, where
    SimNet is charged detailed-trace generation for every new µarch and Tao
    is charged the reusable functional trace once.
"""
from __future__ import annotations

import numpy as np

from repro.core import extract_features
from repro.core.simulate import simulate_trace_legacy
from repro.uarch import UARCH_A, UARCH_B, UARCH_C, get_benchmark, run_detailed, run_functional
from repro.uarch.isa import KIND_NOP, KIND_REAL, KIND_SQUASHED

from .common import (
    EPOCHS,
    TRACE_LEN,
    TRAIN_BENCHES,
    Timer,
    adjusted_dataset,
    emit,
    session,
    tao_config,
)


def run() -> None:
    # --- Fig 10b: trace generation throughput ---------------------------
    func_mips, det_mips = [], []
    sq_frac, nop_frac = [], []
    for bench in TRAIN_BENCHES:
        prog = get_benchmark(bench)
        with Timer() as tf:
            ft = run_functional(prog, TRACE_LEN)
        for uarch in (UARCH_A, UARCH_B, UARCH_C):
            with Timer() as td:
                det, summ = run_detailed(prog, ft, uarch)
            func_mips.append(TRACE_LEN / tf.seconds / 1e6)
            det_mips.append(TRACE_LEN / td.seconds / 1e6)
            kinds = det["kind"]
            extra = (kinds != KIND_REAL).sum()
            if extra:
                sq_frac.append((kinds == KIND_SQUASHED).sum() / extra)
                nop_frac.append((kinds == KIND_NOP).sum() / extra)
    f_mips = float(np.mean(func_mips))
    d_mips = float(np.mean(det_mips))
    ratio = f_mips / d_mips
    emit(
        "fig10b/trace_gen",
        1e6 / (f_mips * 1e6),
        f"functional_mips={f_mips:.3f};detailed_mips={d_mips:.3f};speedup={ratio:.1f}x(paper:25.2x)",
    )
    emit(
        "fig10a/trace_surplus",
        0.0,
        f"squashed_frac={np.mean(sq_frac)*100:.1f}%;nop_frac={np.mean(nop_frac)*100:.1f}%(paper:97.0/3.0)",
    )

    # --- Table 4: overall time, Tao vs SimNet ---------------------------
    cfg = tao_config()
    sess = session()
    # Tao: functional trace (once) + transfer-style short training + sim
    prog = get_benchmark("dee")
    with Timer() as t_func:
        ft = run_functional(prog, TRACE_LEN)
    ds = adjusted_dataset(UARCH_A, TRAIN_BENCHES)
    with Timer() as t_train_short:
        model = sess.train(
            dataset=ds.subsample(max(16, len(ds) // 4)),
            epochs=max(2, EPOCHS // 3), batch_size=16, lr=1e-3,
        )
    engine = model.engine(batch_size=64)
    with Timer() as t_sim:
        ft_test = sess.capture("mcf", TRACE_LEN // 2).functional
        sim = engine.simulate(ft_test)
    tao_total = t_func.seconds + t_train_short.seconds + t_sim.seconds

    # --- engine vs pre-refactor simulate loop (the 18.06x claim's lever) --
    legacy = simulate_trace_legacy(model.params, ft_test, cfg)
    sim2 = engine.simulate(ft_test)  # warm engine: steady-state throughput
    assert engine.num_compiles == 1, engine.num_compiles
    cpi_err = abs(sim2.cpi - legacy.cpi) / max(legacy.cpi, 1e-9)
    emit(
        "engine/sim_throughput",
        1e6 / max(sim2.mips * 1e6, 1e-9),
        f"engine_mips={sim2.mips:.4f};legacy_mips={legacy.mips:.4f};"
        f"speedup={sim2.mips / legacy.mips:.2f}x;compiles={engine.num_compiles};"
        f"cpi_rel_err={cpi_err:.2e}",
    )

    # --- host feature extraction vs the fused device path -----------------
    # The fused engine extracts on device, one megakernel launch per batch
    # feeding the step directly: features never materialize in HBM.  fp32
    # fused is bit-identical to the NumPy engine by contract.
    fcfg = cfg.features
    with Timer() as t_host:
        extract_features(ft_test, fcfg, with_labels=False)
    host_mips = len(ft_test) / 1e6 / t_host.seconds
    mega = model.engine(batch_size=64, feature_backend="fused")
    mega.simulate(ft_test)        # warm-up
    sim_mega = mega.simulate(ft_test)
    assert sim_mega.cpi == sim2.cpi, (sim_mega.cpi, sim2.cpi)
    # host->device traffic: the numpy backend ships the materialized
    # FeatureSet (+ masks); the fused backend ships the raw columns packed
    # as 10 int32 rows per batch.
    host_bpi = 4 * (1 + 32 + 5 + fcfg.n_queue + fcfg.n_mem) + 2
    dev_bpi = 4 * 10
    emit(
        "features/extraction",
        1e6 / max(host_mips * 1e6, 1e-9),
        f"host_mips={host_mips:.4f};"
        f"host_prepass_engine_mips={sim2.mips:.4f};"
        f"fused_engine_speedup={sim_mega.mips / sim2.mips:.2f}x;"
        f"transfer_bytes_per_instr={host_bpi}->{dev_bpi}"
        f"({host_bpi / dev_bpi:.1f}x less)",
    )

    # --- int8 quantized path on the fused backend --------------------------
    q8 = model.engine(batch_size=64, feature_backend="fused", precision="int8")
    q8.simulate(ft_test)          # warm-up (own step: precision is keyed)
    sim_q8 = q8.simulate(ft_test)
    q8_err = abs(sim_q8.cpi - sim_mega.cpi) / max(sim_mega.cpi, 1e-9)
    emit(
        "fused/megakernel",
        1e6 / max(sim_mega.mips * 1e6, 1e-9),
        f"fused_mips={sim_mega.mips:.4f};int8_mips={sim_q8.mips:.4f};"
        f"int8_cpi_rel_err={q8_err:.2e};"
        f"transfer_bytes_per_instr={dev_bpi}",
    )

    # SimNet-style: detailed trace for the new µarch + full training + sim
    with Timer() as t_det:
        run_detailed(prog, ft, UARCH_B)
    with Timer() as t_train_full:
        sess.train(dataset=ds, epochs=EPOCHS, batch_size=16, lr=1e-3)
    simnet_total = t_det.seconds + t_train_full.seconds + t_sim.seconds
    emit(
        "table4/overall",
        tao_total * 1e6,
        f"tao_s={tao_total:.1f};simnet_style_s={simnet_total:.1f};"
        f"speedup={simnet_total/tao_total:.2f}x(paper:18.06x at 10B-instr scale);"
        f"sim_mips={sim.mips:.4f}",
    )
