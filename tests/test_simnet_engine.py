"""SimNet's sequential simulation on the engine (``engine/sequential.py``)
against the plain reference (``bench/reference/simnet.py``), on the CPU at
tiny widths with weights from a seed."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench.reference import simnet as ref  # noqa: E402
from repro.api import TrainedModel  # noqa: E402
from repro.core.align import build_adjusted_trace  # noqa: E402
from repro.core.model import TaoConfig, init_tao  # noqa: E402
from repro.core.simnet import SimNetConfig, pack_rows, teacher_inputs  # noqa: E402
from repro.engine import SimNetEngine, StreamingEngine  # noqa: E402
from repro.engine.sequential import subtrace_sequences  # noqa: E402
from repro.uarch import UARCH_C, get_benchmark, run_detailed, run_functional  # noqa: E402


def _w(cfg: SimNetConfig) -> dict:
    return {"window": cfg.window, "channels": cfg.channels, "n_conv": cfg.n_conv,
            "kernel_size": cfg.kernel_size, "hidden": cfg.hidden, "feat_dim": cfg.feat_dim,
            "subtrace": cfg.subtrace}


@pytest.fixture(scope="module")
def rows():
    prog = get_benchmark("mcf")
    det, _ = run_detailed(prog, run_functional(prog, 1500), UARCH_C)
    return build_adjusted_trace(det).adjusted


def _model(cfg: SimNetConfig, seed: int = 3):
    return jax.jit(lambda k: ref.init_params(k, _w(cfg)))(jax.random.PRNGKey(seed))


@pytest.mark.parametrize("n", [1000, 700])
def test_engine_matches_reference_teacher_forced(rows, n):
    """Every prediction the engine rounded, recomputed by the reference from
    contexts rebuilt from the engine's own latencies.  Both run float32 on
    the CPU; the program convolves with ``lax.conv`` and the reference with
    one matrix product per tap, so their predictions (tens of cycles) differ
    only by summation order, ~1e-5 cycles: a rounding may differ only where
    a prediction lies that close to a half cycle, and 1e-3 leaves room for
    that without admitting a wrong context, which moves predictions by
    cycles (``bench/tests/test_simnet_faults.py``)."""
    cfg = SimNetConfig(window=17, channels=16, hidden=16, subtrace=64)
    params = _model(cfg)
    engine = SimNetEngine(params, cfg, _ecfg(4))
    r = engine.simulate(rows[:n])
    lat = r.latencies()
    want = ref.teacher(params, rows[:n], lat, _w(cfg))
    assert ref.latency_gap(want["raw"], want["lat"]) < 1e-3
    assert r.cpi == want["cpi"]
    assert r.num_instructions == n
    # the predictions move: a constant model would hide a wrong context
    assert len(np.unique(want["lat"][:, 0])) > 3


def _ecfg(batch: int):
    from repro.engine import EngineConfig

    return EngineConfig(batch_size=batch)


def test_free_running_rollout_equals_reference(rows):
    """The engine's integer latencies, step by step, equal the reference's
    own sequential simulation on a short trace (five sub-traces over two
    launches, one a padded tail)."""
    cfg = SimNetConfig(window=9, channels=8, hidden=8, subtrace=24)
    params = _model(cfg, seed=5)
    n = 110
    engine = SimNetEngine(params, cfg, _ecfg(4))
    assert engine.batch_rows(n) == (4, 1)
    r = engine.simulate(rows[:n])
    want = ref.rollout(params, rows[:n], _w(cfg))
    np.testing.assert_array_equal(r.latencies(), want["lat"])
    assert r.cpi == want["cpi"]


def test_clock_and_retire_rule_on_hand_made_latencies():
    """The context is the unretired earlier instructions: an instruction
    that has completed stays while an older one holds retirement."""
    cfg = SimNetConfig(window=5)
    words = jnp.asarray(pack_rows(_fake_rows(4)))
    lat = jnp.asarray([[1, 5], [1, 1], [1, 1], [2, 1]], jnp.int32)
    # F = 1 2 3 5, C = 6 3 4 6, R = 6 6 6 6; instruction 3 reaches fetch at 3
    x, mask = teacher_inputs(words, lat, jnp.arange(4), cfg)
    np.testing.assert_array_equal(np.asarray(mask[3]), [True, True, True, False])
    np.testing.assert_array_equal(np.asarray(mask[1]), [True, False, False, False])
    timing = np.asarray(x[3, 1:4, 30:33])
    # newest first: cycles since fetch, completed, cycles since completion
    np.testing.assert_array_equal(timing, [[0, 0, 0], [1, 1, 0], [2, 0, 0]])
    assert not np.asarray(x[3, 4]).any() and not np.asarray(x[0, 1:]).any()


def test_context_is_capped_at_the_rob():
    """Long completions keep every earlier instruction unretired; the
    context then holds only the newest ``context`` of them."""
    cfg = SimNetConfig(window=129)
    n = 300
    rng = np.random.default_rng(0)
    lat = np.stack([rng.integers(0, 2, n), rng.integers(1, 400, n)], 1).astype(np.int32)
    _, mask = teacher_inputs(jnp.asarray(pack_rows(_fake_rows(n))), jnp.asarray(lat),
                             jnp.arange(n), cfg)
    F = np.cumsum(lat[:, 0])
    R = np.maximum.accumulate(F + lat[:, 1])
    for i in range(n):
        T = F[i - 1] if i else 0
        live = [j for j in range(i - 1, max(i - 1 - cfg.context, -1), -1) if R[j] > T]
        got = np.flatnonzero(np.asarray(mask[i]))
        np.testing.assert_array_equal(i - 1 - got, live)
    assert np.asarray(mask).sum(1).max() == cfg.context


def _full_rob(seed: int = 3):
    """At the cell's ROB (window 129): a model whose completions take ~300
    cycles while it fetches every cycle or so, so more instructions are in
    flight than the context holds and the newest 128 fill it."""
    cfg = SimNetConfig(window=129, channels=8, hidden=8, subtrace=128)
    params = _model(cfg, seed)
    params["head"]["b"] = jnp.asarray([0.5, 300.0], jnp.float32)
    return cfg, params


@pytest.mark.parametrize("fault", [False, True])
def test_scan_at_a_full_rob(rows, fault, monkeypatch):
    """With the context at its cap the scan still meets the reference
    teacher-forced; a context one row short (the ROB's oldest in-flight
    slot dropped) is caught by more than the cell's ``latency_gap`` limit."""
    import repro.core.simnet as S
    from repro.engine import clear_step_cache

    if fault:
        cap = S.in_flight
        monkeypatch.setattr(S, "in_flight", lambda R, T: cap(R, T) & (jnp.arange(R.shape[-1]) < R.shape[-1] - 1))
    clear_step_cache()
    try:
        cfg, params = _full_rob()
        r = SimNetEngine(params, cfg, _ecfg(8)).simulate(rows[:400])
    finally:
        clear_step_cache()
    want = ref.teacher(params, rows[:400], r.latencies(), _w(cfg))
    gap = ref.latency_gap(want["raw"], want["lat"])
    if fault:
        assert gap > 0.12
    else:
        assert r.metrics["context_rows"] > 100 and gap < 1e-3 and r.cpi == want["cpi"]


@pytest.mark.parametrize("n", [1, 64, 100, 257])
def test_subtrace_plan_counts_each_instruction_once(n):
    """Every instruction is counted in exactly one sub-trace, each after
    the ``warmup`` instructions before it, which are not counted."""
    cfg = SimNetConfig(window=9, channels=8, hidden=8, subtrace=32)
    engine = SimNetEngine(_model(cfg), cfg, _ecfg(4))
    plan = engine.batch_rows(n)
    subtraces = -(-n // cfg.subtrace)
    assert sum(plan) >= subtraces and sum(plan) - subtraces < max(1, 4 // 16)
    # as simulate lays out the words: instruction i carries i + 1 in word 1
    flat = np.zeros((cfg.warmup + sum(plan) * cfg.subtrace, 2), np.int32)
    flat[cfg.warmup: cfg.warmup + n] = [[1 << 28, i + 1] for i in range(n)]
    counted, k0 = [], 0
    for r in plan:
        lo = k0 * cfg.subtrace
        seq = np.asarray(subtrace_sequences(jnp.asarray(flat[lo: lo + r * cfg.subtrace + cfg.warmup]), r, cfg))
        for k in range(r):
            ids = seq[k, :, 1] - 1
            g = k0 + k
            warm = ids[: cfg.warmup]
            np.testing.assert_array_equal(
                warm[warm >= 0], [i for i in range(g * cfg.subtrace - cfg.warmup, g * cfg.subtrace) if 0 <= i < n])
            counted += [int(i) for i in ids[cfg.warmup:] if i >= 0]
        k0 += r
    assert counted == list(range(n))


def test_engine_refuses_a_subtrace_shorter_than_its_warmup():
    """Sub-trace k warms on the end of sub-trace k - 1, which must hold a
    whole warm-up."""
    cfg = SimNetConfig(window=33, channels=8, hidden=8, subtrace=16)
    with pytest.raises(ValueError, match="warm-up"):
        SimNetEngine(_model(cfg), cfg, _ecfg(4))


def test_trained_model_serves_simnet_by_its_kind(rows, tmp_path):
    """``TrainedModel`` picks the sequential engine from the config; after
    ``warmup`` a request compiles nothing, and a model with an artifact
    store looks up no host features for it; a Tao model keeps its engine."""
    from repro.store import ArtifactStore

    cfg = SimNetConfig(window=9, channels=8, hidden=8, subtrace=32)
    model = TrainedModel(params=_model(cfg), cfg=cfg, name="simnet", sim_batch_size=4,
                         store=ArtifactStore(str(tmp_path)))
    engine = model.engine(batch_size=4)
    assert isinstance(engine, SimNetEngine)
    engine.warmup(300)
    before = engine.num_compiles
    r = model.simulate(rows[:300])
    assert engine.num_compiles == before and r.num_instructions == 300
    assert {"cpi", "total_cycles", "detailed_branch_mpki", "detailed_l1d_mpki",
            "context_rows"} <= set(r.metrics)
    with pytest.raises(ValueError, match="detailed trace"):
        engine.simulate(run_functional(get_benchmark("mcf"), 300))
    tao = TaoConfig(window=9, d_model=16, n_heads=2, n_layers=1, d_ff=32, d_cat=8)
    tao_engine = TrainedModel(params=init_tao(jax.random.PRNGKey(0), tao), cfg=tao).engine()
    assert type(tao_engine) is StreamingEngine


def _fake_rows(n: int) -> np.ndarray:
    from repro.core.align import ADJ_DTYPE

    out = np.zeros(n, ADJ_DTYPE)
    out["opcode"] = np.arange(n) % 15
    out["dst"] = np.arange(n) % 32
    out["addr"] = 64 * np.arange(n)
    return out
