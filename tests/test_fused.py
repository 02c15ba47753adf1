"""Fused trace->logits megakernel + int8 quantized-path tests.

The tentpole contracts, each enforced bitwise or with a declared band:

  * the fused megakernel == the ``lax.scan`` oracle == the NumPy
    extraction, bit-for-bit, across chunk/length geometry sweeps;
  * batch-granular extraction with the scan state threaded across
    ``FusedExtractor.next_batch`` calls == one monolithic pass;
  * ``feature_backend="fused"`` produces CPI / MPKI / phase curves
    bit-identical to the ``"numpy"`` backend, while
    SHARING their compiled step (one compile per geometry, ever), and its
    extraction is one compiled program per geometry, launched once per
    batch;
  * the int8 W8A8 path holds the ``bench_accuracy`` parity band
    (|dCPI|/CPI <= 5%, |dMPKI| <= max(10%, 5.0)) and gets its own
    step-cache entry (precision is part of the key);
  * a warm server with the fused backend serves with 0 compiles under
    ``sanitized(compile_budget=0)``.
"""
import asyncio

import jax
import numpy as np
import pytest

from repro.core import FeatureConfig, TaoConfig, init_tao
from repro.core.features import extract_features
from repro.engine import (
    EngineConfig,
    StreamingEngine,
    cache_stats,
    clear_step_cache,
)
from repro.kernels.fused.ops import (
    FusedExtractor,
    init_fused_state,
    signed_log_device,
    trace_columns,
)
from repro.kernels.fused.ref import fused_scan_ref, init_state_ref
from repro.uarch import get_benchmark, run_functional
from repro.uarch.isa import FUNC_TRACE_DTYPE, Op

FCFG = FeatureConfig(n_buckets=32, n_queue=4, n_mem=8)
CFG = TaoConfig(
    window=17, d_model=32, n_heads=2, n_layers=1, d_ff=64, d_cat=16, features=FCFG
)

FEATURE_FIELDS = ("opcode", "regbits", "flags", "brhist", "memdist")


def _random_trace(n, rng, branch_p=0.4, mem_p=0.4, pc_mod=64, addr_hi=1 << 20):
    t = np.zeros(n, dtype=FUNC_TRACE_DTYPE)
    t["pc"] = rng.integers(0, pc_mod, n) * 4
    t["opcode"] = rng.integers(0, len(Op), n)
    t["dst"] = rng.integers(0, 32, n)
    t["src1"] = rng.integers(0, 32, n)
    t["src2"] = rng.integers(0, 32, n)
    t["is_branch"] = rng.random(n) < branch_p
    t["taken"] = t["is_branch"] & (rng.random(n) < 0.5)
    t["is_mem"] = ~t["is_branch"] & (rng.random(n) < mem_p)
    t["is_store"] = t["is_mem"] & (rng.random(n) < 0.4)
    t["addr"] = np.where(t["is_mem"], rng.integers(0, addr_hi, n), 0)
    return t


def _assert_bitwise(a, b, msg=""):
    a = np.asarray(a)
    b = np.asarray(b)
    if a.dtype == np.float32:
        np.testing.assert_array_equal(
            a.view(np.int32), b.view(np.int32), err_msg=msg
        )
    else:
        np.testing.assert_array_equal(a, b, err_msg=msg)


def _fused_pass(cols, **kw):
    """One fused pass over all of ``cols``: (features, carry after it)."""
    ex = FusedExtractor(cols, FCFG, **kw)
    return ex.next_batch(len(cols["bucket"])), ex.state


@pytest.fixture(scope="module")
def trace():
    return run_functional(get_benchmark("mcf"), 3000)


@pytest.fixture(scope="module")
def params():
    return init_tao(jax.random.PRNGKey(0), CFG)


# ---------------------------------------------------------------------------
# Layer 1: megakernel vs the scan oracle vs the NumPy extraction
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "n,chunk",
    [(1, 256), (2, 256), (255, 256), (256, 256), (257, 256),
     (1000, 128), (1000, 512), (777, 333)],
)
def test_fused_matches_scan_ref(n, chunk):
    rng = np.random.default_rng(n * 31 + chunk)
    t = _random_trace(n, rng)
    cols = trace_columns(t, FCFG)
    feats, state = _fused_pass(cols, chunk=chunk)
    outcome = np.where(
        t["is_branch"], np.where(t["taken"], 1.0, -1.0), 0.0
    ).astype(np.float32)
    ref, ref_state = fused_scan_ref(
        cols["bucket"], cols["addr"], outcome,
        cols["is_mem"].astype(np.int32),
        init_state_ref(FCFG.n_buckets, FCFG.n_queue, FCFG.n_mem),
        n_mem=FCFG.n_mem,
    )
    _assert_bitwise(feats["brhist"], ref["brhist"], "brhist")
    _assert_bitwise(
        feats["memdist"], signed_log_device(ref["memdist_raw"], np.int32(0)), "memdist"
    )
    # carried state agrees too (table float-exact, queue/fill integer);
    # lanes past N_q / N_m are the kernel's vreg padding
    _assert_bitwise(state["table"][:, : FCFG.n_queue], ref_state[0], "table")
    _assert_bitwise(state["queue"][0, : FCFG.n_mem], ref_state[1], "queue")
    assert int(state["queue"][1, : FCFG.n_mem].sum()) == int(ref_state[2])


@pytest.mark.parametrize("bench", ["mcf", "dee", "lee"])
def test_fused_matches_numpy_bitwise(bench):
    t = run_functional(get_benchmark(bench), 2500)
    ref = extract_features(t, FCFG, with_labels=False)
    fused, _ = _fused_pass(trace_columns(t, FCFG))
    for f in FEATURE_FIELDS:
        _assert_bitwise(fused[f], getattr(ref, f), f"{bench}/{f}")


def test_fused_collision_and_boundary_geometry():
    rng = np.random.default_rng(7)
    for t in (
        _random_trace(4000, rng, branch_p=0.8, mem_p=0.15, pc_mod=8),
        _random_trace(300, rng, branch_p=0.0, mem_p=0.5),
        _random_trace(300, rng, branch_p=0.5, mem_p=0.0),
        _random_trace(1, rng),
    ):
        ref = extract_features(t, FCFG, with_labels=False)
        fused, _ = _fused_pass(trace_columns(t, FCFG))
        for f in FEATURE_FIELDS:
            _assert_bitwise(fused[f], getattr(ref, f), f)


def test_fused_state_threading_across_batches():
    """Uneven batch slices with the carry threaded across megakernel calls
    == one monolithic pass (the streaming-engine contract)."""
    rng = np.random.default_rng(11)
    t = _random_trace(3000, rng)
    cols = trace_columns(t, FCFG)
    one, _ = _fused_pass(cols)

    ex = FusedExtractor(cols, FCFG, pad_to=3300)
    got = {f: [] for f in FEATURE_FIELDS}
    for m in (700, 700, 700, 700, 500):
        b = ex.next_batch(m)
        for f in FEATURE_FIELDS:
            got[f].append(np.asarray(b[f]))
    for f in FEATURE_FIELDS:
        _assert_bitwise(np.concatenate(got[f])[:3000], one[f], f)
    # padded tail is inert, but running past it is a caller bug
    with pytest.raises(ValueError):
        ex.next_batch(301)
    with pytest.raises(ValueError):
        FusedExtractor(cols, FCFG, pad_to=100)


def test_fused_extractor_refuses_past_its_end_and_keeps_its_place():
    """Without ``pad_to`` the extractor ends at the columns' end; a batch
    past it raises before anything is dispatched, so the extractor stays
    where it was and the rest of the trace still comes out exact."""
    rng = np.random.default_rng(13)
    cols = trace_columns(_random_trace(500, rng), FCFG)
    one, _ = _fused_pass(cols)
    ex = FusedExtractor(cols, FCFG)
    first = ex.next_batch(300)
    with pytest.raises(ValueError, match="past the padded column end"):
        ex.next_batch(201)
    rest = ex.next_batch(200)
    for f in FEATURE_FIELDS:
        _assert_bitwise(
            np.concatenate([np.asarray(first[f]), np.asarray(rest[f])]), one[f], f
        )
    with pytest.raises(ValueError, match="past the padded column end"):
        ex.next_batch(1)


def test_fused_extractor_from_a_given_state_equals_the_default():
    """``state=`` a zero carry starts the scan exactly where the default
    does, batch for batch, and the given carry is read, never written (an
    engine hands one zero carry to every request)."""
    rng = np.random.default_rng(12)
    cols = trace_columns(_random_trace(2000, rng), FCFG)
    zero = init_fused_state(FCFG)
    default = FusedExtractor(cols, FCFG, pad_to=2100)
    given = FusedExtractor(cols, FCFG, pad_to=2100, state=zero)
    for m in (700, 700, 700):
        a, b = default.next_batch(m), given.next_batch(m)
        for f in FEATURE_FIELDS + ("is_branch", "is_mem", "valid"):
            _assert_bitwise(b[f], a[f], f)
    for k in zero:
        _assert_bitwise(given.state[k], default.state[k], k)
        assert not np.asarray(zero[k]).any(), k


# ---------------------------------------------------------------------------
# Layer 2: the engine's "fused" backend
# ---------------------------------------------------------------------------

PHASE_METRICS = ("cpi", "branch_mpki", "l1d_mpki", "cpi_phase", "l1d_phase")


@pytest.mark.sanitize
def test_engine_fused_backend_bit_identical(params, trace):
    results = {}
    for backend in ("numpy", "fused"):
        e = StreamingEngine(
            params, CFG,
            EngineConfig(batch_size=13, feature_backend=backend,
                         metrics=PHASE_METRICS),
        )
        results[backend] = e.simulate(trace)
        assert e.num_compiles == 1, (backend, e.num_compiles)
    base, r = results["numpy"], results["fused"]
    for m in ("cpi", "branch_mpki", "l1d_mpki"):
        assert r.metrics[m] == base.metrics[m], m
    for m in ("cpi_phase", "l1d_phase"):
        _assert_bitwise(getattr(r, m), getattr(base, m), m)


def test_engine_fused_collect_arrays_bitwise(params, trace):
    a = StreamingEngine(
        params, CFG,
        EngineConfig(batch_size=16, feature_backend="numpy", collect=True),
    ).simulate(trace)
    b = StreamingEngine(
        params, CFG,
        EngineConfig(batch_size=16, feature_backend="fused", collect=True),
    ).simulate(trace)
    for k in ("fetch_lat", "exec_lat", "mispred_prob", "dlevel"):
        _assert_bitwise(getattr(a, k), getattr(b, k), k)


def test_engine_fused_short_and_ragged_traces(params):
    from repro.core.simulate import simulate_trace

    for n in (1, 5, CFG.window - 1, CFG.window, CFG.window + 1, 400):
        ft = run_functional(get_benchmark("lee"), n)
        a = simulate_trace(params, ft, CFG, batch_size=13,
                           feature_backend="numpy")
        b = simulate_trace(params, ft, CFG, batch_size=13,
                           feature_backend="fused")
        assert a.cpi == b.cpi, n


@pytest.mark.parametrize("order", [("numpy", "fused"), ("fused", "numpy")])
def test_fused_shares_compiled_step_across_backends(params, trace, order):
    """feature_backend stays out of the step-cache key: whichever backend
    runs second reuses the executable the first one compiled — the
    compile-count guard for 'fused = 1 compile per geometry, shared'."""
    # earlier tests may have compiled this exact geometry into the
    # process-wide cache — start cold so the counts are deterministic
    clear_step_cache()
    before = cache_stats()["entries"]
    engines = []
    for backend in order:
        e = StreamingEngine(
            params, CFG, EngineConfig(batch_size=11, feature_backend=backend)
        )
        e.simulate(trace)
        engines.append(e)
    for e in engines:
        assert e.num_compiles == 1         # same shared _CachedStep entry
    assert cache_stats()["entries"] == before + 1


def test_engine_fused_extraction_compiles_once_launches_per_batch(params, monkeypatch):
    """The fused backend's extraction is one compiled program for every
    trace length of a geometry, dispatched once per batch."""
    from repro.kernels.fused import ops

    program = ops._fused_padded
    launches = []

    def counting(*args, **kwargs):
        launches.append(kwargs["shape"])
        return program(*args, **kwargs)

    monkeypatch.setattr(ops, "_fused_padded", counting)
    # a batch size no other test uses: this geometry's program is new here
    bsz = 5
    e = StreamingEngine(
        params, CFG, EngineConfig(batch_size=bsz, feature_backend="fused")
    )
    before = program._cache_size()
    batches = []
    for n in (400, 1300):
        launches.clear()
        e.simulate(run_functional(get_benchmark("mcf"), n))
        batches.append(-(-(n // CFG.window) // bsz))
        assert launches == [(bsz, CFG.window)] * batches[-1], n
    assert batches[0] != batches[1]
    assert program._cache_size() == before + 1


@pytest.mark.parametrize(
    "window,windows,rows",
    [(10, 2 * 16 + 3, (16, 8)), (12, 2 * 16, (16,)), (14, 3, (8,))],
)
def test_warmup_compiles_exactly_the_geometries_simulate_runs(
    window, windows, rows, monkeypatch
):
    """``warmup(n)`` compiles one step per geometry of ``batch_rows(n)`` —
    the full batches, the half-batch tail, or both — and the simulate that
    follows compiles no step; its extraction launches run the same plan."""
    from repro.kernels.fused import ops

    # widths of this test alone, one window per case: fresh step entries
    cfg = TaoConfig(window=window, d_model=32, n_heads=2, n_layers=1, d_ff=56,
                    d_cat=16, features=FCFG)
    n = windows * window + 3
    engine = StreamingEngine(init_tao(jax.random.PRNGKey(0), cfg), cfg,
                             EngineConfig(batch_size=16, feature_backend="fused"))
    entries = engine.warmup(n)
    assert sorted(engine._steps) == sorted((r, window) for r in rows)
    assert len(entries) == len(rows) and engine.num_compiles == len(rows)
    if jax.process_count() == 1:
        assert all(e.aot is not None for e in entries)

    program = ops._fused_padded
    launches = []

    def counting(*args, **kwargs):
        launches.append(kwargs["shape"])
        return program(*args, **kwargs)

    monkeypatch.setattr(ops, "_fused_padded", counting)
    engine.simulate(run_functional(get_benchmark("dee"), n))
    assert engine.num_compiles == len(rows)
    assert launches == [(r, window) for r in engine.batch_rows(n)]
    assert launches[-1] == (rows[-1], window)


def test_engine_rejects_unknown_precision(params):
    with pytest.raises(ValueError, match="precision"):
        StreamingEngine(params, CFG, EngineConfig(precision="fp16"))


# ---------------------------------------------------------------------------
# Layer 3: int8 quantized path
# ---------------------------------------------------------------------------


def test_qdense_matches_fp32_within_band():
    from repro.core.quant import qdense, quantize_dense
    from repro.nn.core import dense

    rng = np.random.default_rng(3)
    p = {
        "w": np.asarray(rng.standard_normal((64, 48)), np.float32),
        "b": np.asarray(rng.standard_normal(48), np.float32),
    }
    x = np.asarray(rng.standard_normal((10, 64)), np.float32)
    qp = quantize_dense(p)
    assert np.asarray(qp["w_q"]).dtype == np.int8
    y32 = np.asarray(dense(p, x))
    y8 = np.asarray(qdense(qp, x))
    # W8A8 keeps ~2 decimal digits on unit-scale data
    err = np.abs(y8 - y32).max() / (np.abs(y32).max() + 1e-9)
    assert err < 0.05, err


def test_quantize_handles_zero_channels():
    from repro.core.quant import qdense, quantize_dense

    p = {"w": np.zeros((8, 4), np.float32)}
    qp = quantize_dense(p)
    y = np.asarray(qdense(qp, np.ones((2, 8), np.float32)))
    assert np.all(y == 0.0) and np.all(np.isfinite(np.asarray(qp["scale"])))


def test_engine_int8_parity_band(params, trace):
    """int8 CPI within 5% relative of fp32; MPKIs within max(10%, 5.0) —
    the same bands ``bench_accuracy``'s fig9 gate enforces on trained
    checkpoints.  The MPKI band is the wide one by design: MPKIs count
    argmax class decisions, which quantization noise flips in whole-event
    steps near decision boundaries (and random-init params, used here,
    put every margin at a coin flip — the worst case)."""
    fp = StreamingEngine(
        params, CFG, EngineConfig(batch_size=16, feature_backend="fused")
    ).simulate(trace)
    q = StreamingEngine(
        params, CFG,
        EngineConfig(batch_size=16, feature_backend="fused", precision="int8"),
    ).simulate(trace)
    assert abs(q.cpi - fp.cpi) / fp.cpi <= 0.05, (q.cpi, fp.cpi)
    for m in ("branch_mpki", "l1d_mpki"):
        a, b = q.metrics[m], fp.metrics[m]
        assert abs(a - b) <= max(0.10 * b, 5.0), (m, a, b)


def test_int8_gets_own_step_cache_entry(params, trace):
    """precision IS part of the step key (int8 bakes a different forward);
    both int8 engines then share one entry across feature backends."""
    clear_step_cache()
    before = cache_stats()["entries"]
    r32 = StreamingEngine(
        params, CFG, EngineConfig(batch_size=9, feature_backend="fused")
    ).simulate(trace)
    q_a = StreamingEngine(
        params, CFG,
        EngineConfig(batch_size=9, feature_backend="fused", precision="int8"),
    )
    q_b = StreamingEngine(
        params, CFG,
        EngineConfig(batch_size=9, feature_backend="numpy", precision="int8"),
    )
    ra = q_a.simulate(trace)
    rb = q_b.simulate(trace)
    assert cache_stats()["entries"] == before + 2   # fp32 + int8, not 3
    assert ra.cpi == rb.cpi                         # backends still bit-equal
    assert ra.cpi != r32.cpi or ra.metrics != r32.metrics


def test_int8_quantized_params_persist_in_store(tmp_path, params, trace):
    """TrainedModel.quantized_params computes the scales once, stores them
    content-addressed, and a second model resolves the same tree."""
    from repro.api.session import TrainedModel, quantized_params_key
    from repro.store import ArtifactStore

    store = ArtifactStore(str(tmp_path))
    m = TrainedModel(params=params, cfg=CFG, name="q", store=store)
    r8 = m.simulate(trace, precision="int8", batch_size=16)
    qk = quantized_params_key(params)
    assert store.has("params_int8", qk)
    m2 = TrainedModel(params=params, cfg=CFG, name="q2", store=store)
    r8b = m2.simulate(trace, precision="int8", batch_size=16)
    assert r8.cpi == r8b.cpi


# ---------------------------------------------------------------------------
# Layer 4: warm serving on the fused backend, compile budget 0
# ---------------------------------------------------------------------------


@pytest.mark.sanitize
def test_warm_serve_fused_zero_compiles(params):
    from repro.analysis.sanitize import sanitized
    from repro.api import ModelRegistry, ServeRequest, Session, TraceServer, TrainedModel

    sess = Session(CFG)
    traces = {
        "long": sess.capture("mcf", 1200),
        "short": sess.capture("lee", 600),
    }
    reg = ModelRegistry()
    reg.register("base", TrainedModel(params=params, cfg=CFG, name="base"))

    async def run():
        server = TraceServer(reg, batch_size=8, feature_backend="fused")
        async with server:
            server.warmup([len(t) for t in traces.values()])
            with sanitized(transfer_guard=None, debug_nans=False,
                           compile_budget=0):
                futs = [
                    server.submit(ServeRequest(model="base", trace=tr))
                    for tr in traces.values()
                ]
                out = await asyncio.gather(*futs)
        return out, server

    out, server = asyncio.run(run())
    assert server.num_compiles == 0
    direct = {
        name: TrainedModel(params=params, cfg=CFG, name="d").simulate(
            tr, batch_size=8, feature_backend="fused"
        )
        for name, tr in traces.items()
    }
    for res, (name, _) in zip(out, traces.items()):
        assert res.metrics["cpi"] == direct[name].cpi, name
