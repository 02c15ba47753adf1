"""Streaming engine tests: vectorized features vs the reference loops,
zero-copy windowing vs the copying grid, legacy-vs-engine metric
equivalence, and the one-compile guarantee."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    FeatureConfig,
    TaoConfig,
    extract_features,
    extract_features_reference,
    init_tao,
    num_windows,
    stream_batches,
    window_view,
)
from repro.core.simulate import simulate_trace, simulate_trace_legacy
from repro.engine import EngineConfig, MetricNotCollectedError, StreamingEngine
from repro.engine.metrics import resolve_metrics
from repro.uarch import get_benchmark, run_functional

FCFG = FeatureConfig(n_buckets=32, n_queue=4, n_mem=8)
CFG = TaoConfig(
    window=17, d_model=32, n_heads=2, n_layers=1, d_ff=64, d_cat=16, features=FCFG
)


@pytest.fixture(scope="module")
def trace():
    return run_functional(get_benchmark("mcf"), 3000)


@pytest.fixture(scope="module")
def params():
    return init_tao(jax.random.PRNGKey(0), CFG)


# ---------------------------------------------------------------------------
# Layer 1: feature extraction
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bench", ["mcf", "dee", "lee"])
def test_vectorized_features_match_reference(bench):
    ft = run_functional(get_benchmark(bench), 2500)
    for cfg in (FCFG, FeatureConfig(n_buckets=2, n_queue=3, n_mem=2)):
        vec = extract_features(ft, cfg, with_labels=False)
        ref = extract_features_reference(ft, cfg, with_labels=False)
        for f in ("opcode", "regbits", "flags", "brhist", "memdist"):
            np.testing.assert_array_equal(
                getattr(vec, f), getattr(ref, f), err_msg=f"{bench}/{f}"
            )


def test_vectorized_features_degenerate_traces():
    from repro.uarch.isa import empty_func_trace

    for n in (0, 1, 2):
        t = empty_func_trace(n)  # no branches, no memory ops
        vec = extract_features(t, FCFG, with_labels=False)
        ref = extract_features_reference(t, FCFG, with_labels=False)
        np.testing.assert_array_equal(vec.brhist, ref.brhist)
        np.testing.assert_array_equal(vec.memdist, ref.memdist)


# ---------------------------------------------------------------------------
# Layer 2: windowing
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "n,window,stride",
    [(100, 16, 16), (100, 16, 4), (100, 16, 1), (15, 16, 16), (16, 16, 16), (17, 16, 16)],
)
def test_window_view_matches_copying_grid(n, window, stride):
    arr = np.arange(n * 3, dtype=np.float32).reshape(n, 3)
    starts = list(range(0, max(1, n - window + 1), stride))
    expect = np.stack([arr[s : s + window] for s in starts])
    got = window_view(arr, window, stride)
    np.testing.assert_array_equal(got, expect)
    assert len(got) == num_windows(n, window, stride)
    # zero-copy: the view shares memory with the source (n >= window case)
    if n >= window:
        assert np.shares_memory(got, arr)


def test_stream_batches_padding_and_masks(trace):
    fs = extract_features(trace, FCFG, with_labels=False)
    W, B = CFG.window, 7
    nw = num_windows(len(trace), W, W)
    assert nw % B != 0  # exercises the ragged final batch
    seen = 0
    for batch in stream_batches(
        fs, W, B, extra={"is_branch": trace["is_branch"]}
    ):
        assert batch["opcode"].shape == (B, W)
        assert batch["is_branch"].shape == (B, W)
        rows = int(batch["valid"][:, 0].sum())
        # valid rows are a prefix; padded rows are fully zero
        assert (batch["valid"][:rows] == 1.0).all()
        assert (batch["valid"][rows:] == 0.0).all()
        assert (batch["opcode"][rows:] == 0).all()
        seen += rows
    assert seen == nw


# ---------------------------------------------------------------------------
# Layer 3: engine vs legacy
# ---------------------------------------------------------------------------


def test_engine_matches_legacy_metrics(params, trace):
    legacy = simulate_trace_legacy(params, trace, CFG, batch_size=64)
    eng = simulate_trace(params, trace, CFG, batch_size=64, collect=True)
    assert eng.num_instructions == legacy.num_instructions
    assert np.isclose(eng.cpi, legacy.cpi, rtol=1e-5)
    assert np.isclose(eng.total_cycles, legacy.total_cycles, rtol=1e-5)
    # counts are integers: padding must not perturb them at all
    assert eng.branch_mpki == legacy.branch_mpki
    assert eng.l1d_mpki == legacy.l1d_mpki
    np.testing.assert_allclose(eng.fetch_lat, legacy.fetch_lat, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(eng.exec_lat, legacy.exec_lat, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        eng.mispred_prob, legacy.mispred_prob, rtol=1e-5, atol=1e-6
    )
    np.testing.assert_array_equal(eng.dlevel, legacy.dlevel)


@pytest.mark.sanitize
def test_engine_single_compile_across_uneven_batches(params, trace):
    engine = StreamingEngine(params, CFG, EngineConfig(batch_size=13))
    r1 = engine.simulate(trace)                                   # ragged tail
    r2 = engine.simulate(run_functional(get_benchmark("dee"), 1000))
    r3 = engine.simulate(run_functional(get_benchmark("lee"), 13 * 17))
    assert engine.num_compiles == 1, engine.num_compiles
    for r in (r1, r2, r3):
        assert np.isfinite(r.cpi) and r.cpi > 0
        # metrics stayed on device: per-instruction arrays not collected
        assert "fetch_lat" not in r.available_metrics
        with pytest.raises(MetricNotCollectedError):
            r.fetch_lat


@pytest.mark.sanitize
def test_engine_collect_off_keeps_metrics_on_device(params, trace):
    eng = simulate_trace(params, trace, CFG, collect=False)
    with pytest.raises(MetricNotCollectedError):
        eng.fetch_lat
    with pytest.raises(MetricNotCollectedError):
        eng.dlevel
    full = simulate_trace(params, trace, CFG, collect=True)
    assert np.isclose(eng.cpi, full.cpi, rtol=1e-6)
    assert eng.branch_mpki == full.branch_mpki


def test_engine_short_trace_matches_legacy(params):
    ft = run_functional(get_benchmark("dee"), 9)  # n < window
    legacy = simulate_trace_legacy(params, ft, CFG)
    eng = simulate_trace(params, ft, CFG)
    assert eng.num_instructions == legacy.num_instructions == 9
    assert np.isclose(eng.cpi, legacy.cpi, rtol=1e-5)


def test_engine_sharded_path_matches(params, trace):
    mesh = jax.make_mesh((1,), ("data",))
    plain = StreamingEngine(params, CFG, EngineConfig(batch_size=16))
    sharded = StreamingEngine(
        params, CFG, EngineConfig(batch_size=16, mesh=mesh, collect=True)
    )
    a = plain.simulate(trace)
    b = sharded.simulate(trace)
    assert np.isclose(a.cpi, b.cpi, rtol=1e-5)
    assert a.branch_mpki == b.branch_mpki
    assert a.l1d_mpki == b.l1d_mpki
    legacy = simulate_trace_legacy(params, trace, CFG)
    np.testing.assert_allclose(b.fetch_lat, legacy.fetch_lat, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# The initial device state: kept per engine, one new value per request
# ---------------------------------------------------------------------------

BACKENDS = ("numpy", "fused")
LENGTHS = (3000, 1000, 40, 2999)


def fresh_carry(ecfg, n):
    """The initial carry built anew, every leaf a new array: what each
    request started from before the engine kept its zeros."""
    carry = {s.name: s.init() for s in resolve_metrics(ecfg.metrics)}
    carry["__grid__"] = {
        "seen": jnp.zeros((), jnp.int32),
        "total": jnp.asarray(num_windows(n, CFG.window, CFG.window), jnp.int32),
    }
    return carry


@pytest.mark.parametrize("backend", BACKENDS)
def test_engine_back_to_back_requests_match_fresh_engines(backend, params, trace):
    """Requests of different lengths on one engine give, bit for bit, what
    a fresh engine gives each of them, and build the zero state once.  A
    later donation of the kept state would fail the second request; the
    phase curve reads the grid's ``total``, so a stale one shows."""
    ecfg = EngineConfig(batch_size=16, feature_backend=backend, collect=True,
                        metrics=("cpi", "branch_mpki", "l1d_mpki", "cpi_phase"))
    engine = StreamingEngine(params, CFG, ecfg)
    got = [engine.simulate(trace[:n]) for n in LENGTHS]
    assert engine.state_builds == 1
    for n, g in zip(LENGTHS, got):
        want = StreamingEngine(params, CFG, ecfg).simulate(trace[:n])
        assert g.num_instructions == want.num_instructions
        assert list(g.metrics) == list(want.metrics)
        for k, v in want.metrics.items():
            np.testing.assert_array_equal(g.metrics[k], v, err_msg=f"{n} {k}")
        for k in ("fetch_lat", "exec_lat", "mispred_prob", "dlevel"):
            np.testing.assert_array_equal(getattr(g, k), getattr(want, k), err_msg=f"{n} {k}")


@pytest.mark.parametrize("backend", BACKENDS)
def test_init_carry_is_the_kept_zeros_and_a_fresh_total(backend, params):
    """``init_carry(n)`` equals the carry built anew, leaf for leaf, with
    the grid's ``total`` of ``n``; edits to a returned carry stay there."""
    ecfg = EngineConfig(batch_size=16, feature_backend=backend)
    engine = StreamingEngine(params, CFG, ecfg)
    for n in (40, 3000):
        carry = engine.init_carry(n)
        assert int(carry["__grid__"]["total"]) == num_windows(n, CFG.window, CFG.window)
        want = fresh_carry(ecfg, n)
        assert jax.tree.structure(carry) == jax.tree.structure(want)
        for a, b in zip(jax.tree.leaves(carry), jax.tree.leaves(want)):
            assert (a.shape, a.dtype) == (b.shape, b.dtype)
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        carry["cpi"]["fetch_sum"] = jnp.float32(1.0)
        carry["__grid__"]["seen"] = jnp.int32(7)
    again = engine.init_carry(40)
    assert float(again["cpi"]["fetch_sum"]) == 0.0
    assert int(again["__grid__"]["seen"]) == 0
    assert engine.state_builds == 1


@pytest.mark.sanitize
def test_engine_feature_backends_bitwise_identical(params, trace):
    """The "fused" backend must reproduce the "numpy" backend exactly:
    same FeatureSet bits in, same jitted step, same metrics out."""
    from repro.kernels.fused.ops import FusedExtractor, trace_columns

    cols = trace_columns(trace, FCFG)
    dev = FusedExtractor(cols, FCFG, chunk=256).next_batch(len(trace))
    host = extract_features(trace, FCFG, with_labels=False)
    for k in ("opcode", "regbits", "flags", "brhist", "memdist"):
        np.testing.assert_array_equal(np.asarray(dev[k]), getattr(host, k), err_msg=k)

    e_np = StreamingEngine(params, CFG, EngineConfig(batch_size=13, collect=True))
    e_fu = StreamingEngine(
        params,
        CFG,
        EngineConfig(batch_size=13, collect=True, feature_backend="fused"),
    )
    a = e_np.simulate(trace)
    b = e_fu.simulate(trace)
    assert a.num_instructions == b.num_instructions
    assert a.cpi == b.cpi
    assert a.total_cycles == b.total_cycles
    assert a.branch_mpki == b.branch_mpki
    assert a.l1d_mpki == b.l1d_mpki
    np.testing.assert_array_equal(a.fetch_lat, b.fetch_lat)
    np.testing.assert_array_equal(a.exec_lat, b.exec_lat)
    np.testing.assert_array_equal(a.mispred_prob, b.mispred_prob)
    np.testing.assert_array_equal(a.dlevel, b.dlevel)


def test_engine_fused_backend_short_and_ragged_traces(params):
    for n in (9, 17, 18, 13 * 17 + 5):
        ft = run_functional(get_benchmark("dee"), n)
        a = simulate_trace(params, ft, CFG, batch_size=13)
        b = simulate_trace(params, ft, CFG, batch_size=13, feature_backend="fused")
        assert a.num_instructions == b.num_instructions
        assert a.cpi == b.cpi, n
        assert a.branch_mpki == b.branch_mpki


def test_engine_fused_backend_wide_address_fallback(params, trace):
    """Addresses outside the int32-exact window: the device backend raises
    instead of silently taking the NumPy path; "numpy" still simulates."""
    t = trace.copy()
    t["addr"][::7] = 2**40
    a = simulate_trace(params, t, CFG, batch_size=16)
    assert np.isfinite(a.cpi)
    with pytest.raises(ValueError, match="2\\^30"):
        simulate_trace(params, t, CFG, batch_size=16, feature_backend="fused")


def test_engine_fused_backend_sharded_matches(params, trace):
    mesh = jax.make_mesh((1,), ("data",))
    plain = StreamingEngine(params, CFG, EngineConfig(batch_size=16))
    sharded = StreamingEngine(
        params,
        CFG,
        EngineConfig(batch_size=16, mesh=mesh, feature_backend="fused"),
    )
    a = plain.simulate(trace)
    b = sharded.simulate(trace)
    assert np.isclose(a.cpi, b.cpi, rtol=1e-6)
    assert a.branch_mpki == b.branch_mpki
    assert a.l1d_mpki == b.l1d_mpki


@pytest.mark.parametrize(
    "backend,match",
    [("cuda", "feature_backend"), ("pallas", 'replaced by "fused"')],
)
def test_engine_rejects_unknown_feature_backend(params, backend, match):
    """Only the two backends are accepted; the staged "pallas" name, which
    a store written earlier may still hold, points at its replacement."""
    with pytest.raises(ValueError, match=match):
        StreamingEngine(params, CFG, EngineConfig(feature_backend=backend))


def test_feature_ops_importable_first():
    """repro.kernels.fused.ops must be importable as the FIRST repro
    import (regression: a module-level ops import in engine.runner closed
    an import cycle through the repro.core package init)."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "-c",
         "import repro.kernels.fused.ops as o; print(o.ADDR_EXACT_LIMIT)"],
        capture_output=True, text=True, timeout=120, env=env,
    )
    assert p.returncode == 0, p.stderr[-2000:]


def test_prefetch_helper_inline_and_threaded():
    """The shared prefetch helper must preserve order in both modes,
    propagate producer errors, and survive an abandoned consumer."""
    from repro.engine.runner import prefetch_to_device

    items = [{"i": np.full((3,), i)} for i in range(25)]
    for threaded in (False, True):
        out = list(
            prefetch_to_device(iter(items), device_put=lambda b: b,
                               threaded=threaded)
        )
        assert [int(o["i"][0]) for o in out] == list(range(25)), threaded
    assert list(prefetch_to_device(iter(()), threaded=True)) == []

    def bad():
        yield {"i": np.zeros(1)}
        raise RuntimeError("producer boom")

    with pytest.raises(RuntimeError, match="producer boom"):
        list(prefetch_to_device(bad(), device_put=lambda b: b, threaded=True))

    gen = prefetch_to_device(iter(items), device_put=lambda b: b, threaded=True)
    assert int(next(gen)["i"][0]) == 0
    gen.close()  # abandoning the consumer must stop the producer thread

    with pytest.raises(ValueError):
        next(prefetch_to_device(iter(items), depth=0, threaded=True))


def test_accelerator_thread_defaults_match_inline(params, trace, monkeypatch):
    """The producer threads an accelerator backend selects by default —
    the engine's threaded prefetch (real device_put) and TraceSweeper's
    async preparation — give the same bits as the inline CPU paths."""
    from repro.engine import SweepJob, TraceSweeper
    from repro.engine import runner

    ecfg = EngineConfig(batch_size=16)
    jobs = [SweepJob("a", params, trace), SweepJob("b", params, trace[:1500])]
    inline = StreamingEngine(params, CFG, ecfg).simulate(trace)
    inline_sweep = TraceSweeper(CFG, ecfg).run(jobs)
    assert not inline_sweep.prepared_async

    threads = []
    real = runner._threaded_prefetch

    def counted(*a, **kw):
        threads.append(1)
        return real(*a, **kw)

    # numpy backend: no Pallas kernel consults the backend in this test
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(runner, "_threaded_prefetch", counted)
    threaded = StreamingEngine(params, CFG, ecfg).simulate(trace)
    sweep = TraceSweeper(CFG, ecfg).run(jobs)
    assert threads and sweep.prepared_async
    assert threaded.metrics == inline.metrics
    for k in ("a", "b"):
        assert sweep.results[k].metrics == inline_sweep.results[k].metrics, k


def test_engine_rejects_mesh_without_data_axis(params):
    mesh = jax.make_mesh((1,), ("model",))
    with pytest.raises(ValueError):
        StreamingEngine(params, CFG, EngineConfig(batch_size=16, mesh=mesh))


def test_engine_multidevice_shard_map():
    """8 placeholder devices: data and pod+data meshes must reproduce the
    legacy metrics exactly (subprocess so XLA device flags apply)."""
    import os
    import subprocess
    import sys
    import textwrap

    script = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax
    from repro.core import TaoConfig, FeatureConfig, init_tao
    from repro.core.simulate import simulate_trace_legacy
    from repro.engine import StreamingEngine, EngineConfig
    from repro.uarch import get_benchmark, run_functional

    fcfg = FeatureConfig(n_buckets=64, n_queue=4, n_mem=8)
    cfg = TaoConfig(window=17, d_model=32, n_heads=2, n_layers=1, d_ff=64,
                    d_cat=16, features=fcfg)
    params = init_tao(jax.random.PRNGKey(0), cfg)
    ft = run_functional(get_benchmark("mcf"), 3000)
    leg = simulate_trace_legacy(params, ft, cfg)
    for shape, names in [((8,), ("data",)), ((2, 4), ("pod", "data"))]:
        mesh = jax.make_mesh(shape, names)
        e = StreamingEngine(params, cfg,
                            EngineConfig(batch_size=32, mesh=mesh))
        r = e.simulate(ft)
        assert abs(r.cpi - leg.cpi) / leg.cpi < 1e-5, (names, r.cpi, leg.cpi)
        assert r.branch_mpki == leg.branch_mpki
        assert r.l1d_mpki == leg.l1d_mpki
        assert e.num_compiles == 1
    print("SHARD_OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"  # placeholder devices; avoid TPU probing
    p = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=560, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    assert "SHARD_OK" in p.stdout
