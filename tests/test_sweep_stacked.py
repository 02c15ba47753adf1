"""Trace-major design-space sweeps (§4.3): one stacked step runs every
design point over each batch of a trace.

``StreamingEngine(heads=K)`` maps the one-model step over K stacked params
trees with the batch shared; ``TraceSweeper`` groups a sweep's jobs by
trace and runs each group as one such simulate.  On the CPU at tiny widths
with seeded random weights: the stacked sweep against one-model runs and
against a plain reference, its extraction and compile counts, resume, the
one-model path, its spans, and a data plan on 8 virtual devices.
"""
import glob
import os
import re
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.api import Session
from repro.core import FeatureConfig, TaoConfig, extract_features, init_tao, num_windows
from repro.core.dataset import INPUT_KEYS
from repro.core.model import tao_forward
from repro.engine import EngineConfig, StreamingEngine, SweepJob, TraceSweeper, cache_stats
from repro.engine.scheduler import stack_params
from repro.store import ArtifactStore
from repro.uarch import get_benchmark, run_functional
from repro.uarch.isa import DLEVEL_L2

# widths of this file alone, so its step-cache entries are its own
FCFG = FeatureConfig(n_buckets=32, n_queue=4, n_mem=8)
CFG = TaoConfig(window=15, d_model=32, n_heads=2, n_layers=2, d_ff=64, d_cat=16, features=FCFG)
BATCH = 16
K = 3
METRICS = ("cpi", "branch_mpki", "l1d_mpki")

# The stacked step is one XLA program over K heads (a batched matmul per
# layer).  XLA tiles and fuses it apart from the one-head program, so a
# float32 sum may round differently in its last bits (a few ulp, ~1e-7
# relative) for the same model math: stacked and one-model results agree
# to a relative 1e-6, not bit for bit.
REL = 1e-6


@pytest.fixture(scope="module")
def traces():
    return {"mcf": run_functional(get_benchmark("mcf"), 1500),
            "lee": run_functional(get_benchmark("lee"), 1100)}


@pytest.fixture(scope="module")
def models():
    return [init_tao(jax.random.PRNGKey(10 + i), CFG) for i in range(K)]


def jobs_of(models, traces):
    return [SweepJob(f"m{i}/{t}", p, tr)
            for i, p in enumerate(models) for t, tr in traces.items()]


def batches_of(trace, cfg=CFG):
    return -(-num_windows(len(trace), cfg.window, cfg.window) // BATCH)


@pytest.mark.parametrize("backend", ["numpy", "fused"])
def test_stacked_sweep_equals_one_model_runs(backend, models, traces):
    ecfg = EngineConfig(batch_size=BATCH, feature_backend=backend)
    rep = TraceSweeper(CFG, ecfg).run(jobs_of(models, traces))
    assert rep.heads_per_step == K and rep.stacks_built == 1
    assert list(rep.results) == [j.key for j in jobs_of(models, traces)]
    for i, p in enumerate(models):
        one = StreamingEngine(p, CFG, ecfg)
        for t, tr in traces.items():
            want, got = one.simulate(tr), rep.results[f"m{i}/{t}"]
            assert got.num_instructions == want.num_instructions
            for m in METRICS:
                assert got.metrics[m] == pytest.approx(want.metrics[m], rel=REL, abs=0), (i, t, m)


def reference(params, trace):
    """CPI, branch and L1D MPKI from NumPy features and ``tao_forward`` at
    the highest matmul precision, over the engine's window grid."""
    w = CFG.window
    nw = num_windows(len(trace), w, w)
    n = nw * w
    fs = extract_features(trace, FCFG, with_labels=False)
    batch = {k: getattr(fs, k)[:n].reshape((nw, w) + getattr(fs, k).shape[1:]) for k in INPUT_KEYS}
    with jax.default_matmul_precision("highest"):
        out = jax.jit(lambda p, b: tao_forward(p, b, CFG))(params, batch)
    fetch = np.maximum(np.asarray(out["fetch_lat"], np.float64).reshape(-1), 0.0)
    execl = np.maximum(np.asarray(out["exec_lat"], np.float64).reshape(-1), 0.0)
    misp = np.asarray(out["mispred_logit"]).reshape(-1) > 0.0
    dlev = np.asarray(out["dlevel_logits"]).argmax(-1).reshape(-1)
    br, mem = trace["is_branch"][:n], trace["is_mem"][:n]
    return {"cpi": (fetch.sum() + execl[-1]) / n,
            "branch_mpki": 1000.0 * (misp & br).sum() / n,
            "l1d_mpki": 1000.0 * ((dlev >= DLEVEL_L2) & mem).sum() / n}


def test_stacked_sweep_matches_plain_reference(models, traces):
    """The engine's tolerance against its reference (``test_engine``):
    CPI to 1e-5 relative (float32 partial sums), the counts exactly."""
    rep = TraceSweeper(CFG, EngineConfig(batch_size=BATCH, feature_backend="fused")).run(
        jobs_of(models, traces))
    for i, p in enumerate(models):
        for t, tr in traces.items():
            want, got = reference(p, tr), rep.results[f"m{i}/{t}"]
            assert got.cpi == pytest.approx(want["cpi"], rel=1e-5)
            assert got.branch_mpki == pytest.approx(want["branch_mpki"], rel=1e-12)
            assert got.l1d_mpki == pytest.approx(want["l1d_mpki"], rel=1e-12)


def test_extractions_are_traces_times_batches(models, traces):
    ecfg = EngineConfig(batch_size=BATCH, feature_backend="fused")
    rep = TraceSweeper(ecfg=ecfg, cfg=CFG).run(jobs_of(models, traces))
    per_trace = sum(batches_of(tr) for tr in traces.values())
    assert rep.extractions == per_trace  # not K x traces x batches
    # one engine per model would have run the extraction K times over
    engines = [StreamingEngine(p, CFG, ecfg) for p in models]
    for e in engines:
        for tr in traces.values():
            e.simulate(tr)
    assert sum(e.extractions for e in engines) == K * per_trace


def test_one_compile_per_geometry_and_warm_sweep_compiles_nothing():
    cfg = TaoConfig(window=13, d_model=32, n_heads=2, n_layers=1, d_ff=64, d_cat=16,
                    features=FCFG)
    sess = Session(cfg, batch_size=BATCH, feature_backend="fused")
    models = {f"u{i}": sess.init_model(seed=i, name=f"u{i}") for i in range(K)}
    trs = [sess.capture("mcf", 1500), sess.capture("dee", 900)]
    rep = sess.sweep(models, trs)
    assert rep.num_compiles == 1 and rep.stacks_built == 1
    assert rep.heads_per_step == K and len(rep.results) == K * 2
    again = sess.sweep(models, trs)
    # the session kept its sweeper and the sweeper its stack
    assert again.num_compiles == 0 and again.stacks_built == 0
    for key, r in rep.results.items():
        assert again.results[key].metrics == r.metrics, key


@pytest.mark.parametrize("via", ["session", "sweeper"])
def test_warmup_with_heads_makes_the_first_sweep_compile_nothing(via):
    """Zero cold start for sweeps: warm-up given the sweep's model count
    compiles the stacked step the sweep then runs."""
    window = {"session": 9, "sweeper": 7}[via]  # each case its own step-cache entries
    cfg = TaoConfig(window=window, d_model=32, n_heads=2, n_layers=1, d_ff=48, d_cat=16,
                    features=FCFG)
    sess = Session(cfg, batch_size=BATCH, feature_backend="fused")
    models = {f"w{i}": sess.init_model(seed=20 + i, name=f"w{i}") for i in range(K)}
    tr = sess.capture("lee", 1100)
    if via == "session":
        sess.warmup([len(tr.functional)], heads=K)
        rep = sess.sweep(models, [tr])
    else:
        sweeper = TraceSweeper(cfg, EngineConfig(batch_size=BATCH, feature_backend="fused"))
        sweeper.warmup([len(tr.functional)], heads=K)
        rep = sweeper.run([SweepJob(n, m.params, tr.functional) for n, m in models.items()])
    assert rep.num_compiles == 0 and rep.heads_per_step == K, rep.stats()


def test_one_model_sweep_runs_the_one_model_step(traces):
    cfg = TaoConfig(window=11, d_model=32, n_heads=2, n_layers=1, d_ff=64, d_cat=16,
                    features=FCFG)
    sess = Session(cfg, batch_size=BATCH, feature_backend="fused")
    mdl = sess.init_model(seed=3, name="solo")
    tr = sess.capture("mcf", 1200)
    solo = mdl.simulate(tr)
    entries = cache_stats()["entries"]
    rep = sess.sweep([mdl], [tr])
    assert rep.num_compiles == 0 and cache_stats()["entries"] == entries
    assert rep.heads_per_step == 1 and rep.stacks_built == 0
    assert rep.results[f"solo/{tr.name}"].metrics == solo.metrics


def test_resume_with_a_partial_done_set(tmp_path, models, traces):
    jobs = jobs_of(models, traces)
    ecfg = EngineConfig(batch_size=BATCH)
    ref = TraceSweeper(CFG, ecfg).run(jobs)
    st = ArtifactStore(str(tmp_path / "s"))
    done = {"m0/mcf", "m1/mcf", "m2/lee"}
    first = TraceSweeper(CFG, ecfg, store=st).run(
        [j for j in jobs if j.key in done], resume_key="dse")
    assert first.heads_per_step == pytest.approx((2 * batches_of(traces["mcf"])
                                                  + batches_of(traces["lee"]))
                                                 / (batches_of(traces["mcf"])
                                                    + batches_of(traces["lee"])))
    resumed = TraceSweeper(CFG, ecfg, store=st).run(jobs, resume_key="dse")
    assert resumed.jobs_skipped == len(done) and resumed.num_traces == len(jobs)
    # the rest: m2 alone on mcf (one-model step), m0 and m1 stacked on lee
    assert resumed.stacks_built == 1 and resumed.features_extracted == 0
    assert set(resumed.results) == {j.key for j in jobs}
    for key, r in ref.results.items():
        for m in METRICS:
            assert resumed.results[key].metrics[m] == pytest.approx(r.metrics[m], rel=REL, abs=0)


@pytest.mark.parametrize("backend", ["numpy", "fused"])
def test_stacked_engine_per_head_arrays_and_checks(backend, models, traces):
    """Each head's per-instruction arrays equal its one-model engine's, on
    host-extracted batches and on fused ones (what the sweep cell runs)."""
    tr = traces["lee"]
    ecfg = EngineConfig(batch_size=BATCH, collect=True, feature_backend=backend)
    stacked = StreamingEngine(stack_params(models), CFG, ecfg, heads=K)
    out = stacked.simulate_heads(tr)
    assert len(out) == K
    for p, got in zip(models, out):
        want = StreamingEngine(p, CFG, ecfg).simulate(tr)
        np.testing.assert_allclose(got.fetch_lat, want.fetch_lat, rtol=REL, atol=1e-6)
        np.testing.assert_array_equal(got.dlevel, want.dlevel)
    with pytest.raises(ValueError, match="simulate_heads"):
        stacked.simulate(tr)
    with pytest.raises(ValueError, match="leading axis"):
        StreamingEngine(models[0], CFG, ecfg, heads=K)
    with pytest.raises(ValueError, match="heads"):
        StreamingEngine(models[0], CFG, ecfg, heads=0)


@pytest.mark.parametrize("backend", ["numpy", "fused"])
def test_stacked_engine_back_to_back_keeps_one_zero_state(backend, models, traces):
    """Requests of different lengths on one K-head engine give, bit for
    bit, what a fresh K-head engine gives each, with the zero state built
    once and the grid's ``total`` one per head."""
    ecfg = EngineConfig(batch_size=BATCH, feature_backend=backend)
    stacked = stack_params(models)
    engine = StreamingEngine(stacked, CFG, ecfg, heads=K)
    reqs = [traces["mcf"], traces["lee"][:700], traces["mcf"][:200]]
    got = [engine.simulate_heads(tr) for tr in reqs]
    assert engine.state_builds == 1
    for tr, g in zip(reqs, got):
        want = StreamingEngine(stacked, CFG, ecfg, heads=K).simulate_heads(tr)
        assert [r.metrics for r in g] == [r.metrics for r in want], len(tr)
    for n in (200, 1500):
        total = np.asarray(engine.init_carry(n)["__grid__"]["total"])
        assert total.dtype == np.int32
        np.testing.assert_array_equal(total, [num_windows(n, CFG.window, CFG.window)] * K)
    assert engine.state_builds == 1


def test_sweep_spans(models, traces, tmp_path):
    sweeper = TraceSweeper(CFG, EngineConfig(batch_size=BATCH, feature_backend="fused"))
    jobs = jobs_of(models, traces)
    sweeper.run(jobs)  # compile outside the profile
    with jax.profiler.trace(str(tmp_path)):
        sweeper.run(jobs)
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True))[-1]
    evs = sorted((e.start_ns, e.end_ns, e.name[4:], dict(e.stats))
                 for plane in ProfileData.from_file(path).planes if plane.name.startswith("/host:")
                 for line in plane.lines for e in line.events if e.name.startswith("tao/"))
    (call,) = [e for e in evs if e[2] == "sweep.call"]
    assert (call[3]["jobs"], call[3]["traces"], call[3]["heads"]) == (len(jobs), 2, K)
    groups = [e for e in evs if e[2] == "sweep.group"]
    assert [g[3]["heads"] for g in groups] == [K, K]
    assert [g[3]["batches"] for g in groups] == [batches_of(tr) for tr in traces.values()]
    sims = [e for e in evs if e[2] == "engine.simulate"]
    assert [s[3]["heads"] for s in sims] == [K, K]
    for g, s in zip(groups, sims):
        assert call[0] <= g[0] <= s[0] and s[1] <= g[1] <= call[1]
        assert g[3]["call"] == call[3]["call"]
        steps = [e for e in evs if e[2] == "engine.step" and s[0] <= e[0] and e[1] <= s[1]]
        assert len(steps) == g[3]["batches"]


def test_stacked_step_keeps_the_module_name_the_readers_match(models):
    engine = StreamingEngine(stack_params(models), CFG, EngineConfig(batch_size=BATCH), heads=K)
    entry = engine.warmup(1000)
    assert re.search(r"HloModule (\S+?),", entry.aot.as_text()).group(1) == "jit_body"


def test_sharded_stacked_sweep_subprocess():
    """8 virtual CPU devices, a data plan, the fused backend: the stacked
    sweep gives what one device gives, bit for bit, with one compile."""
    script = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    from repro.api import Session
    from repro.core import TaoConfig, FeatureConfig
    from repro.distributed import data_mesh

    cfg = TaoConfig(window=15, d_model=32, n_heads=2, n_layers=1, d_ff=64, d_cat=16,
                    features=FeatureConfig(n_buckets=32, n_queue=4, n_mem=8))
    mesh = data_mesh()
    assert dict(mesh.shape) == {"data": 8}
    one = Session(cfg, batch_size=16, feature_backend="fused")
    sharded = Session(cfg, batch_size=16, feature_backend="fused", mesh=mesh)
    models = {f"m{i}": one.init_model(seed=i, name=f"m{i}") for i in range(3)}
    traces = {"mcf": one.capture("mcf", 1500), "dee": one.capture("dee", 1000)}
    a = one.sweep(models, traces)
    b = sharded.sweep(models, traces)
    assert b.plan_kind == "sharded" and b.num_shards == 8, b.stats()
    assert b.num_compiles == 1 and b.heads_per_step == 3, b.stats()
    assert b.extractions == a.extractions, (a.stats(), b.stats())
    for key, r in a.results.items():
        assert b.results[key].metrics == r.metrics, key
    print("SHARDED_SWEEP_OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "-c", script], capture_output=True, text=True,
                       timeout=560, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    assert "SHARDED_SWEEP_OK" in p.stdout
