"""ExecutionPlan partitioning layer tests.

Three tiers:
  * pure plan resolution / distributed helpers (always run);
  * in-process multi-device tests, active when the process already has
    >= 8 XLA devices (the ``shard-cpu`` CI job runs the suite under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``);
  * one subprocess acceptance test that runs everywhere: single-device
    vs 8-virtual-device plans must produce identical metrics — CPI/MPKI
    and windowed phase curves — on both feature backends, with the
    one-compile-per-geometry guarantee intact, plus a data-sharded
    ``Session.sweep`` and a plan-parallel trainer run.
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core import FeatureConfig, TaoConfig, init_tao, num_windows
from repro.distributed import data_mesh, initialize_multihost, topology_info
from repro.engine import (
    DEFAULT_PHASE_CHUNKS,
    EngineConfig,
    ExecutionPlan,
    StreamingEngine,
    windowed_spec,
)
from repro.uarch import get_benchmark, run_functional

FCFG = FeatureConfig(n_buckets=32, n_queue=4, n_mem=8)
CFG = TaoConfig(
    window=17, d_model=32, n_heads=2, n_layers=1, d_ff=64, d_cat=16, features=FCFG
)

multidevice = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs >= 8 XLA devices (shard-cpu CI job sets "
    "XLA_FLAGS=--xla_force_host_platform_device_count=8)",
)


@pytest.fixture(scope="module")
def trace():
    return run_functional(get_benchmark("mcf"), 3000)


@pytest.fixture(scope="module")
def params():
    return init_tao(jax.random.PRNGKey(0), CFG)


# ---------------------------------------------------------------------------
# Plan resolution (pure, single device)
# ---------------------------------------------------------------------------


def test_single_plan_properties():
    plan = ExecutionPlan.resolve(None, batch_size=16)
    assert not plan.sharded
    assert plan.kind == "single"
    assert plan.num_shards == 1
    assert plan.local_batch(16) == 16
    assert plan.batch_sharding() is None
    actx = plan.axis_context()
    x = np.float32(3.0)
    assert actx.psum(x) is x and actx.pmax(x) is x
    assert int(actx.shard_index()) == 0
    plan.validate_batch(7)  # anything divides 1 shard


def test_sharded_plan_resolution():
    mesh = jax.make_mesh((1,), ("data",))
    plan = ExecutionPlan.resolve(mesh, batch_size=16)
    assert plan.sharded and plan.batch_axes == ("data",)
    assert plan.num_shards == 1
    assert plan.batch_sharding() is not None
    assert plan.describe()["mesh_shape"] == {"data": 1}
    # resolving the same mesh again gives an EQUAL plan (step-cache key)
    assert plan == ExecutionPlan.resolve(mesh, batch_size=16)
    # a resolved plan passes through resolve()
    assert ExecutionPlan.resolve(None, batch_size=16, plan=plan) is plan


def test_plan_rejects_mesh_without_batch_axis():
    mesh = jax.make_mesh((1,), ("model",))
    with pytest.raises(ValueError, match="batch"):
        ExecutionPlan.resolve(mesh, batch_size=16)


def test_plan_rejects_conflicting_mesh_and_plan():
    mesh = jax.make_mesh((1,), ("data",))
    other = jax.make_mesh((1,), ("pod", "data")[-1:])  # distinct object, equal
    plan = ExecutionPlan.resolve(mesh, batch_size=16)
    # an equal mesh is fine; a *different* one is rejected
    assert ExecutionPlan.resolve(other, batch_size=16, plan=plan) is plan
    model_mesh = jax.make_mesh((1,), ("model",))
    with pytest.raises(ValueError, match="plan"):
        ExecutionPlan.resolve(model_mesh, batch_size=16, plan=plan)


def test_plan_constructor_invariants():
    with pytest.raises(ValueError):
        ExecutionPlan(kind="weird")
    with pytest.raises(ValueError):
        ExecutionPlan(kind="sharded")  # no mesh/axes
    with pytest.raises(ValueError):
        ExecutionPlan(kind="single", mesh=jax.make_mesh((1,), ("data",)))


def test_engine_shares_step_across_mesh_and_plan_spelling(params, trace):
    """EngineConfig(mesh=m) and EngineConfig(plan=resolve(m)) must hit the
    same step-cache entry — the plan, not the spelling, is the key."""
    mesh = jax.make_mesh((1,), ("data",))
    plan = ExecutionPlan.resolve(mesh, batch_size=19)
    e_mesh = StreamingEngine(params, CFG, EngineConfig(batch_size=19, mesh=mesh))
    e_plan = StreamingEngine(params, CFG, EngineConfig(batch_size=19, plan=plan))
    e_mesh.simulate(trace)
    e_plan.simulate(trace)
    assert e_mesh.num_compiles == 1
    assert e_plan.num_compiles == 1  # same shared entry, no second trace


# ---------------------------------------------------------------------------
# Distributed helpers
# ---------------------------------------------------------------------------


def test_initialize_multihost_single_process_fallback():
    info = initialize_multihost()
    assert not info.initialized
    assert info.process_count == 1 and info.process_index == 0
    assert not info.is_multihost
    # idempotent
    assert initialize_multihost() is info
    # ... but an explicit cluster request after the fallback must not be
    # silently swallowed by the cache
    with pytest.raises(RuntimeError, match="single-process"):
        initialize_multihost(coordinator_address="example:1234", num_processes=2)


def test_plan_auto_matches_device_count():
    plan = ExecutionPlan.auto(batch_size=jax.device_count() * 2)
    if jax.device_count() > 1:
        assert plan.sharded
        assert plan.num_shards == jax.device_count()
        assert plan == ExecutionPlan.resolve(
            data_mesh(), batch_size=jax.device_count() * 2
        )
    else:
        assert plan == ExecutionPlan.single()


def test_data_mesh_shapes():
    mesh = data_mesh(1)
    assert dict(mesh.shape) == {"data": 1}
    with pytest.raises(ValueError):
        data_mesh(0)
    with pytest.raises(ValueError):
        data_mesh(3, pods=2)  # 3 devices don't split into 2 pods


def test_topology_info_keys():
    info = topology_info()
    assert info["device_count"] >= 1
    assert set(info) >= {"backend", "process_count", "default_plan"}
    assert info["default_plan"]["kind"] in ("single", "sharded")
    assert "mesh_shape" in info["default_plan"]
    # with an explicit plan, the actual plan is recorded verbatim
    info = topology_info(plan=ExecutionPlan.single())
    assert info["plan"] == ExecutionPlan.single().describe()
    assert "default_plan" not in info


def test_virtual_cpu_devices_too_late_raises():
    from repro.distributed import virtual_cpu_devices

    saved = {k: os.environ.get(k) for k in ("XLA_FLAGS", "JAX_PLATFORMS")}
    try:
        have = jax.device_count()  # backend is initialized by now
        with pytest.raises(RuntimeError, match="XLA_FLAGS"):
            virtual_cpu_devices(have + 1)
        assert virtual_cpu_devices(have) == have  # satisfiable is fine
        with pytest.raises(ValueError):
            virtual_cpu_devices(0)
    finally:  # don't leak the flags into envs later subprocesses inherit
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


# ---------------------------------------------------------------------------
# Windowed (phase-curve) MetricSpecs
# ---------------------------------------------------------------------------


def test_windowed_metric_stays_on_device_and_matches_oracle(params, trace):
    """cpi_phase must equal the host oracle computed from the collected
    per-instruction arrays — while itself never requiring collect=True."""
    nc = DEFAULT_PHASE_CHUNKS
    e = StreamingEngine(
        params,
        CFG,
        EngineConfig(batch_size=13, collect=True, metrics=("cpi", "cpi_phase")),
    )
    res = e.simulate(trace)
    curve = res.cpi_phase
    assert curve.shape == (nc,) and curve.dtype == np.float32

    w_eff = min(CFG.window, len(trace))
    nw = num_windows(len(trace), CFG.window, CFG.window)
    count = nw * w_eff
    win = np.arange(count) // w_eff
    chunk = np.clip(win * nc // nw, 0, nc - 1)
    sums = np.bincount(chunk, weights=res.fetch_lat.astype(np.float64), minlength=nc)
    cnts = np.bincount(chunk, minlength=nc)
    oracle = sums / np.maximum(cnts, 1)
    np.testing.assert_allclose(curve, oracle, rtol=1e-5, atol=1e-5)

    # the same curve with collect=False: metrics on device all the way
    e2 = StreamingEngine(
        params, CFG, EngineConfig(batch_size=13, metrics=("cpi", "cpi_phase"))
    )
    res2 = e2.simulate(trace)
    np.testing.assert_array_equal(res2.cpi_phase, curve)
    assert "fetch_lat" not in res2.available_metrics
    # numpy and fused backends agree bit-for-bit on the curve
    e3 = StreamingEngine(
        params,
        CFG,
        EngineConfig(
            batch_size=13, feature_backend="fused", metrics=("cpi", "cpi_phase")
        ),
    )
    np.testing.assert_array_equal(e3.simulate(trace).cpi_phase, curve)


def test_windowed_metric_short_and_ragged_traces(params):
    for n in (9, 17, 13 * 17 + 5):
        ft = run_functional(get_benchmark("dee"), n)
        e = StreamingEngine(
            params, CFG, EngineConfig(batch_size=13, metrics=("cpi", "l1d_phase"))
        )
        r = e.simulate(ft)
        assert r.l1d_phase.shape == (DEFAULT_PHASE_CHUNKS,)
        assert np.all(np.isfinite(r.l1d_phase))


def test_windowed_spec_factory_validation():
    with pytest.raises(ValueError):
        windowed_spec("bad", lambda ctx: ctx.fetch_lat, num_chunks=0)


def test_l1d_phase_is_rate_over_memory_ops(params, trace):
    """l1d_phase's denominator population is memory ops (count=is_mem),
    not all instructions — checked against the collected arrays."""
    nc = DEFAULT_PHASE_CHUNKS
    e = StreamingEngine(
        params,
        CFG,
        EngineConfig(batch_size=13, collect=True, metrics=("cpi", "l1d_phase")),
    )
    res = e.simulate(trace)
    count = res.num_instructions
    w_eff = min(CFG.window, len(trace))
    nw = num_windows(len(trace), CFG.window, CFG.window)
    chunk = np.clip((np.arange(count) // w_eff) * nc // nw, 0, nc - 1)
    from repro.uarch.isa import DLEVEL_L2

    mem = trace["is_mem"][:count]
    miss = (res.dlevel >= DLEVEL_L2) & mem
    misses = np.bincount(chunk, weights=miss.astype(np.float64), minlength=nc)
    mems = np.bincount(chunk, weights=mem.astype(np.float64), minlength=nc)
    oracle = misses / np.maximum(mems, 1)
    np.testing.assert_allclose(res.l1d_phase, oracle, rtol=1e-6, atol=1e-7)


def test_windowed_chunk_index_envelope_enforced(params):
    """num_windows * num_chunks must fit int32 — the engine refuses the
    trace instead of letting chunk_of silently wrap."""
    huge = windowed_spec(
        "huge_phase", lambda ctx: ctx.fetch_lat, num_chunks=2**31 - 1
    )
    e = StreamingEngine(params, CFG, EngineConfig(metrics=(huge,)))
    with pytest.raises(ValueError, match="envelope"):
        e.init_carry(CFG.window * 2)  # nw=2 -> 2 * (2^31-1) overflows


def test_grid_key_is_reserved(params):
    from repro.engine.metrics import MetricSpec

    bad = MetricSpec(
        name="__grid__",
        init=lambda: 0,
        update=lambda c, ctx: c,
        finalize=lambda c, n: {},
    )
    with pytest.raises(ValueError, match="reserved"):
        StreamingEngine(params, CFG, EngineConfig(metrics=("cpi", bad)))


def test_custom_windowed_spec_num_chunks(params, trace):
    spec = windowed_spec(
        "mispred_phase", lambda ctx: ctx.mispred_prob, num_chunks=7
    )
    e = StreamingEngine(params, CFG, EngineConfig(batch_size=16, metrics=(spec,)))
    r = e.simulate(trace)
    assert r.mispred_phase.shape == (7,)
    assert np.all((r.mispred_phase >= 0) & (r.mispred_phase <= 1))


# ---------------------------------------------------------------------------
# In-process multi-device (active under the shard-cpu CI job)
# ---------------------------------------------------------------------------

METRICS = ("cpi", "branch_mpki", "l1d_mpki", "cpi_phase", "l1d_phase")


@multidevice
def test_plans_bit_identical_metrics_inprocess(params, trace):
    single = StreamingEngine(
        params, CFG, EngineConfig(batch_size=32, metrics=METRICS)
    )
    a = single.simulate(trace)
    for mesh in (data_mesh(), data_mesh(pods=2)):
        sharded = StreamingEngine(
            params, CFG, EngineConfig(batch_size=32, mesh=mesh, metrics=METRICS)
        )
        b = sharded.simulate(trace)
        assert a.cpi == b.cpi, dict(mesh.shape)
        assert a.branch_mpki == b.branch_mpki
        assert a.l1d_mpki == b.l1d_mpki
        np.testing.assert_array_equal(a.cpi_phase, b.cpi_phase)
        np.testing.assert_array_equal(a.l1d_phase, b.l1d_phase)
        assert sharded.num_compiles == 1


@multidevice
def test_sharded_sweep_inprocess(trace):
    from repro.api import Session

    sess = Session(CFG, batch_size=32, mesh=data_mesh())
    assert sess.plan is not None and sess.plan.sharded
    models = {f"m{i}": sess.init_model(seed=i, name=f"m{i}") for i in range(2)}
    traces = {
        "mcf": sess.capture("mcf", 1500),
        "dee": sess.capture("dee", 1200),
    }
    report = sess.sweep(models, traces)
    assert report.plan_kind == "sharded"
    assert report.num_shards == 8
    assert report.num_compiles <= 1  # one geometry -> at most one compile
    # every pair agrees with a direct sharded simulate
    for mn, mdl in models.items():
        for tn, tr in traces.items():
            direct = mdl.simulate(tr)
            assert report.results[f"{mn}/{tn}"].cpi == direct.cpi


# ---------------------------------------------------------------------------
# Subprocess acceptance (runs on any host)
# ---------------------------------------------------------------------------


def test_sharded_plan_keeps_one_zero_state_subprocess():
    """8 virtual devices, a data plan, every feature backend: requests of
    different lengths on one sharded engine give, bit for bit, what a
    fresh sharded engine and the single-device plan give each; the zero
    state is built once and every carry leaf, ``total`` included, is
    replicated over the mesh."""
    script = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    from repro.core import TaoConfig, FeatureConfig, init_tao, num_windows
    from repro.distributed import data_mesh
    from repro.engine import StreamingEngine, EngineConfig
    from repro.uarch import get_benchmark, run_functional

    cfg = TaoConfig(window=17, d_model=32, n_heads=2, n_layers=1, d_ff=64,
                    d_cat=16, features=FeatureConfig(n_buckets=64, n_queue=4, n_mem=8))
    params = init_tao(jax.random.PRNGKey(0), cfg)
    ft = run_functional(get_benchmark("mcf"), 2000)
    lengths = (2000, 700, 30)
    mesh = data_mesh()
    for backend in ("numpy", "fused"):
        ecfg = EngineConfig(batch_size=32, mesh=mesh, feature_backend=backend)
        engine = StreamingEngine(params, cfg, ecfg)
        single = StreamingEngine(params, cfg, EngineConfig(batch_size=32,
                                                           feature_backend=backend))
        for n in lengths:
            got = engine.simulate(ft[:n]).metrics
            assert got == StreamingEngine(params, cfg, ecfg).simulate(ft[:n]).metrics, (backend, n)
            assert got == single.simulate(ft[:n]).metrics, (backend, n)
        assert engine.state_builds == 1, (backend, engine.state_builds)
        assert engine.num_compiles == 1, (backend, engine.num_compiles)
        carry = engine.init_carry(1000)
        assert int(carry["__grid__"]["total"]) == num_windows(1000, 17, 17)
        for leaf in jax.tree.leaves(carry):
            assert leaf.sharding.is_fully_replicated, backend
            assert len(leaf.sharding.device_set) == 8, backend
    print("ZERO_STATE_OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"  # virtual devices; avoid TPU probing
    p = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=560, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    assert "ZERO_STATE_OK" in p.stdout


def test_plans_acceptance_subprocess():
    """Single-device vs 8-virtual-device shard_map plan: identical CPI /
    MPKI and windowed phase curves on BOTH feature backends, one compile
    per geometry, a data-sharded Session.sweep (2 models x 2 traces, one
    compile), and a plan-parallel trainer run with its compile guard."""
    script = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax
    from repro.api import Session
    from repro.core import TaoConfig, FeatureConfig, init_tao
    from repro.core.transfer import train_tao_impl
    from repro.core.dataset import build_windows
    from repro.core.features import extract_features
    from repro.core.align import build_adjusted_trace
    from repro.distributed import data_mesh
    from repro.engine import StreamingEngine, EngineConfig, ExecutionPlan
    from repro.train.trainer import train_step_compiles
    from repro.uarch import UARCH_A, get_benchmark, run_functional, run_detailed

    fcfg = FeatureConfig(n_buckets=64, n_queue=4, n_mem=8)
    cfg = TaoConfig(window=17, d_model=32, n_heads=2, n_layers=1, d_ff=64,
                    d_cat=16, features=fcfg)
    params = init_tao(jax.random.PRNGKey(0), cfg)
    ft = run_functional(get_benchmark("mcf"), 3000)
    METRICS = ("cpi", "branch_mpki", "l1d_mpki", "cpi_phase", "l1d_phase")

    mesh = data_mesh()
    assert dict(mesh.shape) == {"data": 8}

    # 1. bit-identical metrics across plans, both backends
    a = StreamingEngine(params, cfg, EngineConfig(
        batch_size=32, metrics=METRICS)).simulate(ft)
    for backend in ("numpy", "fused"):
        e = StreamingEngine(params, cfg, EngineConfig(
            batch_size=32, mesh=mesh, feature_backend=backend,
            metrics=METRICS))
        b = e.simulate(ft)
        assert b.cpi == a.cpi, (backend, b.cpi, a.cpi)
        assert b.branch_mpki == a.branch_mpki
        assert b.l1d_mpki == a.l1d_mpki
        assert np.array_equal(b.cpi_phase, a.cpi_phase), backend
        assert np.array_equal(b.l1d_phase, a.l1d_phase), backend
        assert e.num_compiles == 1, (backend, e.num_compiles)

    # 2. data-sharded Session.sweep: 2 models x 2 traces, one compile.
    # batch_size=16 is a FRESH geometry (part 1 used 32), so the single
    # compile below is attributable to the sweep alone.
    sess = Session(cfg, batch_size=16, mesh=mesh)
    models = {f"m{i}": sess.init_model(seed=i, name=f"m{i}") for i in range(2)}
    traces = {"mcf": sess.capture("mcf", 1500), "dee": sess.capture("dee", 1200)}
    report = sess.sweep(models, traces, metrics=METRICS)
    assert report.plan_kind == "sharded" and report.num_shards == 8
    assert report.num_compiles == 1, report.num_compiles
    for mn, mdl in models.items():
        for tn, tr in traces.items():
            assert report.results[f"{mn}/{tn}"].cpi == mdl.simulate(
                tr, metrics=METRICS).cpi

    # windowed curves came off-device without collect=True
    r = report.results["m0/mcf"]
    assert r.cpi_phase.shape == (32,)
    assert "fetch_lat" not in r.available_metrics

    # 3. trainer under the plan: same batch stream, grads all-reduced
    prog = get_benchmark("lee")
    t = run_functional(prog, 2000)
    det, _ = run_detailed(prog, t, UARCH_A)
    ds = build_windows(
        extract_features(build_adjusted_trace(det).adjusted, fcfg), cfg.window)
    plan = ExecutionPlan.resolve(mesh, batch_size=16)
    c0 = train_step_compiles()
    ref = train_tao_impl(cfg, ds, epochs=2, batch_size=16, seed=0)
    par = train_tao_impl(cfg, ds, epochs=2, batch_size=16, seed=0, plan=plan)
    # one trace for the unsharded entry + one for the plan's entry
    assert train_step_compiles() - c0 == 2, train_step_compiles() - c0
    np.testing.assert_allclose(par.losses, ref.losses, rtol=1e-4)
    print("PLAN_OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"  # virtual devices; avoid TPU probing
    p = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=560, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    assert "PLAN_OK" in p.stdout
