"""repro.api facade + MetricSpec registry tests.

Covers the PR-3 surface: Session capture/dataset/train/train_joint/sweep,
TrainedModel simulate/transfer, the pluggable metric registry (built-in
specs bit-for-bit against the legacy carry, custom specs against NumPy
oracles), SimulationResult ergonomics, and the deprecation shims."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (
    DesignSpace,
    EngineConfig,
    MetricNotCollectedError,
    MetricNotComputedError,
    MetricSpec,
    Session,
    TrainedModel,
    register_metric,
)
from repro.core import FeatureConfig, TaoConfig, init_tao, tao_forward
from repro.core.dataset import INPUT_KEYS, num_windows, stream_batches
from repro.core.features import extract_features
from repro.engine import METRIC_REGISTRY, SimulationResult, StreamingEngine
from repro.engine.metrics import resolve_metrics
from repro.uarch import UARCH_A, UARCH_B, get_benchmark, run_functional
from repro.uarch.isa import DLEVEL_L2, NUM_DLEVELS

FCFG = FeatureConfig(n_buckets=32, n_queue=4, n_mem=8)
CFG = TaoConfig(
    window=17, d_model=32, n_heads=2, n_layers=1, d_ff=64, d_cat=16, features=FCFG
)


@pytest.fixture(scope="module")
def sess():
    return Session(CFG)


@pytest.fixture(scope="module")
def trace(sess):
    return sess.capture("mcf", 3000)


@pytest.fixture(scope="module")
def params():
    return init_tao(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def model(params):
    return TrainedModel(params=params, cfg=CFG, name="m0")


# ---------------------------------------------------------------------------
# Built-in MetricSpecs vs the legacy carry (bit-for-bit)
# ---------------------------------------------------------------------------


def _legacy_carry_metrics(params, func_trace, cfg, batch_size):
    """Verbatim reimplementation of the pre-registry engine step (the
    hardcoded 4-scalar carry of PR 1/2) as a NumPy-driven jax oracle."""
    fs = extract_features(func_trace, cfg.features, with_labels=False)
    n = len(func_trace)
    w_eff = min(cfg.window, n)
    count = num_windows(n, cfg.window, cfg.window) * w_eff

    @jax.jit
    def body(params, carry, batch):
        valid = batch["valid"].reshape(-1)
        out = tao_forward(params, {k: batch[k] for k in INPUT_KEYS}, cfg)
        fetch = jnp.maximum(out["fetch_lat"], 0.0).reshape(-1)
        execl = jnp.maximum(out["exec_lat"], 0.0).reshape(-1)
        misp = jax.nn.sigmoid(out["mispred_logit"]).reshape(-1)
        dlev = jnp.argmax(out["dlevel_logits"], -1).astype(jnp.int32).reshape(-1)
        on = valid > 0
        br = batch["is_branch"].reshape(-1) & on
        mem = batch["is_mem"].reshape(-1) & on
        gidx = jnp.arange(valid.shape[0], dtype=jnp.float32)
        last_key = jnp.max(jnp.where(on, gidx, -1.0))
        part = {
            "fetch_sum": (fetch * valid).sum(dtype=jnp.float32),
            "mispred": ((misp > 0.5) & br).sum(dtype=jnp.int32),
            "l1d": ((dlev >= DLEVEL_L2) & mem).sum(dtype=jnp.int32),
        }
        exec_tail = execl[jnp.argmax(jnp.where(on, gidx, -1.0)).astype(jnp.int32)]
        new_carry = {k: carry[k] + part[k] for k in part}
        new_carry["last_exec"] = jnp.where(last_key >= 0, exec_tail, carry["last_exec"])
        return new_carry

    carry = {
        "fetch_sum": jnp.zeros((), jnp.float32),
        "mispred": jnp.zeros((), jnp.int32),
        "l1d": jnp.zeros((), jnp.int32),
        "last_exec": jnp.zeros((), jnp.float32),
    }
    for batch in stream_batches(
        fs, cfg.window, batch_size, stride=cfg.window,
        extra={"is_branch": func_trace["is_branch"], "is_mem": func_trace["is_mem"]},
    ):
        carry = body(params, carry, batch)
    carry = jax.device_get(carry)
    total = float(carry["fetch_sum"] + carry["last_exec"])
    return {
        "cpi": total / max(count, 1),
        "total_cycles": total,
        "branch_mpki": 1000.0 * float(carry["mispred"]) / max(count, 1),
        "l1d_mpki": 1000.0 * float(carry["l1d"]) / max(count, 1),
    }


@pytest.mark.parametrize("bench,n,bsz", [("mcf", 3000, 64), ("dee", 1000, 13), ("lee", 13 * 17, 13)])
@pytest.mark.parametrize("backend", ["numpy", "fused"])
def test_builtin_specs_match_legacy_carry_bitwise(params, bench, n, bsz, backend):
    ft = run_functional(get_benchmark(bench), n)
    oracle = _legacy_carry_metrics(params, ft, CFG, bsz)
    res = StreamingEngine(
        params, CFG, EngineConfig(batch_size=bsz, feature_backend=backend)
    ).simulate(ft)
    for k, v in oracle.items():
        assert res.metrics[k] == v, (k, backend)


# ---------------------------------------------------------------------------
# Custom MetricSpecs (defined here, not in engine/) vs NumPy oracles
# ---------------------------------------------------------------------------


def test_custom_metric_spec_matches_numpy_oracle(params, trace):
    hi_lat = MetricSpec(
        name="hi_lat",
        init=lambda: jnp.zeros((), jnp.int32),
        update=lambda c, ctx: c
        + ctx.psum(((ctx.fetch_lat > 2.0) & ctx.on).sum(dtype=jnp.int32)),
        finalize=lambda c, n: {
            "hi_lat_count": float(c),
            "hi_lat_frac": float(c) / max(n, 1),
        },
    )
    mdl = TrainedModel(params=params, cfg=CFG)
    res = mdl.simulate(
        trace, collect=True, batch_size=13,
        metrics=("cpi", "branch_mpki", "l1d_mpki", hi_lat),
    )
    # NumPy oracle from the collected per-instruction predictions
    expect = int((res.fetch_lat > 2.0).sum())
    assert res.hi_lat_count == expect
    assert res.hi_lat_frac == expect / res.num_instructions
    assert res.metrics["cpi"] == res.cpi  # built-ins still present


def test_custom_vector_carry_spec_taken_branches(params, trace):
    """A spec with a pytree carry reading raw batch columns (ctx.batch)."""
    taken = MetricSpec(
        name="taken",
        init=lambda: {"n": jnp.zeros((), jnp.int32)},
        update=lambda c, ctx: {
            "n": c["n"]
            + ctx.psum(
                (ctx.batch["taken"].reshape(-1).astype(bool) & ctx.is_branch)
                .sum(dtype=jnp.int32)
            )
        },
        finalize=lambda c, n: {"taken_branches": float(c["n"])},
    )
    ft = trace.functional
    # the engine only ships is_branch/is_mem by default; pass taken through
    # the features extra path by simulating off raw trace windows
    fs = extract_features(ft, CFG.features, with_labels=False)
    n = len(ft)
    count = num_windows(n, CFG.window, CFG.window) * min(CFG.window, n)

    engine = StreamingEngine(
        params, CFG, EngineConfig(batch_size=16, metrics=("cpi", taken))
    )
    # init_carry includes the engine's reserved window-grid slot; driving
    # the step off a hand-built spec dict is no longer valid
    carry = engine.init_carry(n)
    step = engine._get_step(min(CFG.window, n))
    for batch in stream_batches(
        fs, CFG.window, 16, stride=CFG.window,
        extra={
            "is_branch": ft["is_branch"],
            "is_mem": ft["is_mem"],
            "taken": ft["taken"],
        },
    ):
        carry, _ = step(engine.params, carry, batch)
    carry = jax.device_get(carry)
    got = taken.finalize(carry["taken"], count)["taken_branches"]
    expect = float((ft["taken"][:count] & ft["is_branch"][:count]).sum())
    assert got == expect


def test_registered_dlevel_hist_matches_oracle(params, trace):
    mdl = TrainedModel(params=params, cfg=CFG)
    res = mdl.simulate(trace, collect=True, metrics=("cpi", "dlevel_hist"))
    ft = trace.functional
    mem = ft["is_mem"][: res.num_instructions]
    oracle = np.bincount(res.dlevel[mem], minlength=NUM_DLEVELS)
    names = ("dlevel_none", "dlevel_l1", "dlevel_l2", "dlevel_dram")
    for i, name in enumerate(names):
        assert res.metrics[name] == float(oracle[i])


def test_finalize_output_key_collision_rejected(params, trace):
    clashing = MetricSpec(
        name="cycles2",
        init=lambda: jnp.zeros((), jnp.float32),
        update=lambda c, ctx: c + ctx.psum((ctx.exec_lat * ctx.valid).sum()),
        finalize=lambda c, n: {"total_cycles": float(c)},  # cpi also emits it
    )
    mdl = TrainedModel(params=params, cfg=CFG)
    with pytest.raises(ValueError, match="total_cycles"):
        mdl.simulate(trace, metrics=("cpi", clashing))


def test_metric_registry_errors(params):
    with pytest.raises(KeyError):
        StreamingEngine(params, CFG, EngineConfig(metrics=("nope",)))
    with pytest.raises(ValueError):
        resolve_metrics(("cpi", "cpi"))
    with pytest.raises(ValueError):
        resolve_metrics(())
    with pytest.raises(TypeError):
        resolve_metrics((42,))
    with pytest.raises(ValueError):
        register_metric(METRIC_REGISTRY["cpi"])  # already registered
    assert set(("cpi", "branch_mpki", "l1d_mpki", "dlevel_hist")) <= set(
        METRIC_REGISTRY
    )


# ---------------------------------------------------------------------------
# SimulationResult ergonomics
# ---------------------------------------------------------------------------


def test_result_uncollected_metric_raises_clear_error(model, trace):
    res = model.simulate(trace, collect=False)
    assert set(res.available_metrics) == {
        "cpi", "total_cycles", "branch_mpki", "l1d_mpki"
    }
    with pytest.raises(MetricNotCollectedError, match="collect=True"):
        res.fetch_lat
    with pytest.raises(MetricNotCollectedError):
        res.mispred_prob
    with pytest.raises(MetricNotComputedError, match="available_metrics"):
        res.dlevel_none  # spec not requested
    with pytest.raises(AttributeError):
        res.definitely_not_a_metric


def test_result_collected_metrics_accessible(model, trace):
    res = model.simulate(trace, collect=True)
    assert "fetch_lat" in res.available_metrics
    assert res.fetch_lat.shape == (res.num_instructions,)
    assert res.dlevel.dtype == np.int32
    assert res.cpi == res.metrics["cpi"]
    assert "cpi" in repr(res) and "fetch_lat" in repr(res)


def test_result_legacy_constructor_kwargs():
    r = SimulationResult(
        num_instructions=10, seconds=1.0, mips=1e-5,
        cpi=2.0, total_cycles=20.0, branch_mpki=1.0, l1d_mpki=0.5,
        fetch_lat=np.ones(10, np.float32),
    )
    assert r.cpi == 2.0 and r.metrics["total_cycles"] == 20.0
    assert r.fetch_lat.sum() == 10.0
    assert r.error_vs(4.0) == 50.0
    with pytest.raises(MetricNotCollectedError):
        r.exec_lat


# ---------------------------------------------------------------------------
# Deprecation shims
# ---------------------------------------------------------------------------


def test_simulate_trace_shim_warns_and_matches(params, trace, model):
    from repro.core import simulate_trace

    with pytest.warns(DeprecationWarning, match="repro.api"):
        old = simulate_trace(params, trace.functional, CFG, batch_size=13)
    new = model.simulate(trace, collect=True, batch_size=13)
    assert old.num_instructions == new.num_instructions
    assert old.cpi == new.cpi
    assert old.branch_mpki == new.branch_mpki
    assert old.l1d_mpki == new.l1d_mpki
    np.testing.assert_array_equal(old.fetch_lat, new.fetch_lat)


def test_train_tao_shim_warns_and_matches(sess, trace):
    from repro.core import train_tao

    ds = sess.dataset(UARCH_A, trace).subsample(16)
    with pytest.warns(DeprecationWarning, match="Session.train"):
        old = train_tao(CFG, ds, epochs=2, batch_size=8, lr=2e-3, seed=3)
    new = sess.train(dataset=ds, epochs=2, batch_size=8, lr=2e-3, seed=3)
    assert old.losses == new.losses
    for a, b in zip(jax.tree.leaves(old.params), jax.tree.leaves(new.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_facade_emits_no_deprecation_warnings(sess, trace, model):
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        ds = sess.dataset(UARCH_A, trace).subsample(8)
        sess.train(dataset=ds, epochs=1, batch_size=8)
        model.simulate(trace)
    ours = [
        w for w in rec
        if issubclass(w.category, DeprecationWarning) and "repro" in str(w.message)
    ]
    assert not ours, [str(w.message) for w in ours]


# ---------------------------------------------------------------------------
# Session workflow
# ---------------------------------------------------------------------------


def test_capture_is_cached_and_reusable(sess):
    a = sess.capture("dee", 1200)
    b = sess.capture("dee", 1200)
    assert a is b
    assert a.num_instructions == len(a) == 1200
    assert sess.capture("dee", 800) is not a
    # a custom name never shadows (or inherits) the default-named capture
    named = sess.capture("dee", 1200, name="warmup")
    assert named.name == "warmup" and named is not a
    assert sess.capture("dee", 1200).name == "dee:1200"
    assert sess.capture("dee", 1200, name="warmup") is named


def test_capture_distinct_programs_same_name_do_not_alias(sess):
    import copy

    prog = get_benchmark("dee")
    prog2 = copy.copy(prog)  # distinct object, same .name
    a = sess.capture(prog, 600)
    b = sess.capture(prog2, 600)
    assert a is not b
    assert a.program is prog and b.program is prog2
    assert sess.capture(prog, 600) is a  # same object still caches


def test_model_sim_batch_size_follows_session(trace):
    cfg = TaoConfig(
        window=29, d_model=32, n_heads=2, n_layers=1, d_ff=64, d_cat=16,
        features=FCFG,
    )
    sess = Session(cfg, batch_size=16)
    mdl = sess.init_model()
    assert mdl.sim_batch_size == 16
    mdl.simulate(trace)  # compiles the (batch=16, w_eff) step
    # the sweep uses the same executable: zero additional compiles
    report = sess.sweep([mdl], [sess.capture("mcf", 1500)])
    assert report.num_compiles == 0


def test_train_and_transfer_freeze_embed(sess, trace):
    ds = sess.dataset(UARCH_A, trace)
    mdl = sess.train(UARCH_A, [trace], epochs=1, batch_size=8, lr=1e-3)
    assert mdl.uarch == UARCH_A and len(mdl.losses) == 1
    ft = sess.train(dataset=ds.subsample(8), epochs=1, batch_size=4, init=mdl)
    assert np.isfinite(ft.losses[-1])
    tr = mdl.transfer(ds.subsample(8), epochs=1, batch_size=4)
    for a, b in zip(
        jax.tree.leaves(mdl.params["embed"]), jax.tree.leaves(tr.params["embed"])
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    res = tr.simulate(trace)
    assert np.isfinite(res.cpi) and res.cpi > 0


def test_dataset_cache_distinguishes_same_named_traces(sess):
    a = sess.capture("dee", 900, name="x")
    b = sess.capture("lee", 900, name="x")
    ds_a = sess.dataset(UARCH_A, [a])
    ds_b = sess.dataset(UARCH_A, [b])
    assert ds_a is sess.dataset(UARCH_A, [a])  # cache hit on same object
    assert ds_a is not ds_b  # same name, different trace -> different data
    assert not np.array_equal(ds_a.inputs["opcode"], ds_b.inputs["opcode"])


def test_joint_eval_loss_mirrors_training_adapt_usage(sess, trace):
    """Only method='tao' trains the adaptation layers, so only it may eval
    through them (gradnorm & co. would otherwise score random params)."""
    from repro.core.multiarch import eval_loss as core_eval

    ds = sess.dataset(UARCH_A, trace).subsample(8)
    batches = []
    for b in ds.batches(4):
        b["labels"] = {k: jnp.asarray(v) for k, v in b.pop("labels").items()}
        batches.append(b)
        break
    for method, use_adapt in (("gradnorm", False), ("tao", True)):
        joint = sess.train_joint(
            UARCH_A, UARCH_B, datasets=(ds, ds), method=method,
            epochs=1, batch_size=4,
        )
        got = joint.eval_loss(batches, "A")
        want = core_eval(joint.params, batches, CFG, "A", use_adapt=use_adapt)
        assert got == want, method


def test_train_joint_on_epoch_hook(sess, trace):
    ds = sess.dataset(UARCH_A, trace).subsample(8)
    seen = []
    sess.train_joint(
        UARCH_A, UARCH_B, datasets=(ds, ds), epochs=2, batch_size=4,
        on_epoch=lambda ep, params, steps: seen.append((ep, steps)),
    )
    assert [e for e, _ in seen] == [0, 1]
    assert seen[-1][1] > 0


def test_train_joint_rejects_dataset_smaller_than_batch(sess, trace):
    ds = sess.dataset(UARCH_A, trace).subsample(4)
    with pytest.raises(ValueError, match="no full batch"):
        sess.train_joint(UARCH_A, UARCH_B, datasets=(ds, ds), epochs=1,
                         batch_size=64)


def test_joint_transfer_rejects_bad_donor(sess, trace):
    ds = sess.dataset(UARCH_A, trace).subsample(8)
    joint = sess.train_joint(UARCH_A, UARCH_B, datasets=(ds, ds), epochs=1,
                             batch_size=4)
    with pytest.raises(ValueError, match="donor"):
        joint.transfer(ds, donor="embed")


def test_joint_head_requires_trained_adapt(sess, trace):
    """Non-tao methods never train the adaptation layers, so head() would
    silently simulate through random weights — it must refuse."""
    ds = sess.dataset(UARCH_A, trace).subsample(8)
    joint = sess.train_joint(UARCH_A, UARCH_B, datasets=(ds, ds),
                             method="granite", epochs=1, batch_size=4)
    with pytest.raises(ValueError, match="adaptation"):
        joint.head("A")
    # transfer() is fine: it fine-tunes the adapt layers it initializes
    mdl = joint.transfer(ds, epochs=1, batch_size=4)
    assert np.isfinite(mdl.losses[-1])


def test_finalize_reserved_key_rejected(params, trace):
    shadowing = MetricSpec(
        name="walltime",
        init=lambda: jnp.zeros((), jnp.float32),
        update=lambda c, ctx: c,
        finalize=lambda c, n: {"seconds": float(c)},  # instance attr wins
    )
    mdl = TrainedModel(params=params, cfg=CFG)
    with pytest.raises(ValueError, match="reserved"):
        mdl.simulate(trace, metrics=("cpi", shadowing))


def test_ground_truth_and_dataset_share_one_detailed_run(monkeypatch, trace):
    import repro.api.session as api_session

    sess = Session(CFG)
    tr = sess.capture("dee", 800)
    calls = []
    real = api_session.run_detailed

    def counting(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(api_session, "run_detailed", counting)
    summ = sess.ground_truth(UARCH_A, tr)
    sess.dataset(UARCH_A, [tr])
    assert summ == sess.ground_truth(UARCH_A, tr)
    assert len(calls) == 1  # one detailed sim serves truth + dataset


def test_session_feature_backend_stamped_on_models(trace):
    sess = Session(CFG, feature_backend="fused")
    mdl = sess.init_model()
    assert mdl.sim_feature_backend == "fused"
    # both paths produce identical metrics (backends are bit-identical)
    a = mdl.simulate(trace)                            # fused via default
    b = mdl.simulate(trace, feature_backend="numpy")   # explicit override
    assert a.cpi == b.cpi and a.l1d_mpki == b.l1d_mpki


def test_design_space_select_pair_caches_measurement(monkeypatch):
    import repro.api.session as api_session

    space = DesignSpace.sample(3, seed=5)
    calls = []
    real = api_session.measure_design_metrics

    def counting(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(api_session, "measure_design_metrics", counting)
    a = space.select_pair(["dee"], method="mahalanobis", instructions=500)
    b = space.select_pair(["dee"], method="euclidean", instructions=500)
    assert len(calls) == 1  # one detailed-sim pass serves both methods
    assert a and b


def test_train_joint_and_transfer(sess, trace):
    joint = sess.train_joint(
        UARCH_A, UARCH_B, [trace], method="tao", epochs=1, batch_size=8
    )
    assert len(joint.losses) == 1 and joint.steps > 0
    head = joint.head("A")
    assert np.isfinite(head.simulate(trace).cpi)
    small = sess.dataset(UARCH_B, trace).subsample(8)
    mdl = joint.transfer(small, epochs=1, batch_size=4)
    for a, b in zip(
        jax.tree.leaves(joint.embedding), jax.tree.leaves(mdl.params["embed"])
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError):
        joint.head("C")


def test_train_requires_data(sess):
    with pytest.raises(ValueError, match="dataset"):
        sess.train(epochs=1)


def test_design_space_helpers():
    space = DesignSpace.vary(UARCH_B, "l1d_size", [1024, 2048, 4096])
    assert len(space) == 3
    assert [d.l1d_size for d in space] == [1024, 2048, 4096]
    assert space[0].name == "l1d_size1024"
    sampled = DesignSpace.sample(5, seed=1)
    i, j = sampled.select_pair(["dee"], method="random", seed=2)
    assert i != j and 0 <= i < 5 and 0 <= j < 5
    with pytest.raises(ValueError):
        sampled.select_pair(["dee"], method="cosine")


# ---------------------------------------------------------------------------
# Async multi-trace sweep
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("window,async_prepare", [(19, False), (23, True)])
def test_sweep_four_uarchs_two_traces_single_compile(trace, window, async_prepare):
    # fresh config per mode -> fresh step-cache entry, so the compile count
    # below is attributable to this sweep alone (inline and threaded modes)
    cfg = TaoConfig(
        window=window, d_model=32, n_heads=2, n_layers=1, d_ff=64, d_cat=16,
        features=FCFG,
    )
    sess = Session(cfg, batch_size=16)
    models = {f"u{i}": sess.init_model(seed=i, name=f"u{i}") for i in range(4)}
    traces = [sess.capture("mcf", 1500), sess.capture("lee", 1100)]
    report = sess.sweep(models, traces, async_prepare=async_prepare)

    assert report.prepared_async == async_prepare
    assert report.num_traces == 8 and len(report.results) == 8
    assert report.num_compiles == 1  # one executable for the whole sweep
    assert report.traces_per_s > 0 and report.mips > 0
    assert 0.0 <= report.queue_occupancy_mean <= report.queue_depth
    assert report.queue_occupancy_max <= report.queue_depth
    # results identical to the single-trace engine path
    for name, mdl in models.items():
        for tr in traces:
            swept = report.results[f"{name}/{tr.name}"]
            solo = mdl.simulate(tr, batch_size=16)
            assert swept.cpi == solo.cpi
            assert swept.branch_mpki == solo.branch_mpki
            assert swept.l1d_mpki == solo.l1d_mpki
    assert report.stats()["num_compiles"] == 1
    # a second sweep over the warm cache compiles nothing
    again = sess.sweep(models, traces, async_prepare=async_prepare)
    assert again.num_compiles == 0


def test_sweep_rejects_duplicate_model_names(sess, trace, params):
    a = TrainedModel(params=params, cfg=CFG, name="tao")
    b = TrainedModel(params=params, cfg=CFG, name="tao")
    with pytest.raises(ValueError, match="duplicate model name"):
        sess.sweep([a, b], [trace])


def test_model_num_compiles_dedupes_shared_steps(params):
    # fresh config -> fresh cache entries attributable to this model alone
    cfg = TaoConfig(
        window=23, d_model=32, n_heads=2, n_layers=1, d_ff=64, d_cat=16,
        features=FCFG,
    )
    mdl = TrainedModel(params=init_tao(jax.random.PRNGKey(0), cfg), cfg=cfg)
    ft = run_functional(get_benchmark("dee"), 500)
    mdl.simulate(ft, batch_size=16)
    mdl.simulate(ft, batch_size=16, feature_backend="fused")
    # two engines, one shared executable (the step-cache key excludes the
    # feature backend) -> one compile, not two
    assert len(mdl._engines) == 2
    assert mdl.num_compiles == 1


def test_sweep_rejects_mismatched_config(sess, trace, params):
    other_cfg = TaoConfig(
        window=21, d_model=32, n_heads=2, n_layers=1, d_ff=64, d_cat=16,
        features=FCFG,
    )
    alien = TrainedModel(params=params, cfg=other_cfg, name="alien")
    with pytest.raises(ValueError, match="different TaoConfig"):
        sess.sweep([alien], [trace])


def test_sweep_duplicate_keys_rejected(sess, trace, model):
    from repro.engine import SweepJob, TraceSweeper

    sweeper = TraceSweeper(CFG, EngineConfig(batch_size=16))
    jobs = [
        SweepJob("same", model.params, trace.functional),
        SweepJob("same", model.params, trace.functional),
    ]
    with pytest.raises(ValueError, match="duplicate"):
        sweeper.run(jobs)
    with pytest.raises(ValueError):
        sweeper.run([])
    with pytest.raises(ValueError):
        TraceSweeper(CFG, EngineConfig(), depth=0)


@pytest.mark.parametrize("async_prepare", [False, True])
def test_sweep_consumer_error_propagates(sess, model, async_prepare):
    """A failing job must abort the sweep cleanly in both prepare modes
    (threaded mode must not leave the producer parked on a full queue)."""
    import threading

    good = sess.capture("dee", 400).functional
    bad = np.zeros(0, dtype=good.dtype)
    from repro.engine import SweepJob, TraceSweeper

    sweeper = TraceSweeper(
        CFG, EngineConfig(batch_size=16), async_prepare=async_prepare
    )
    jobs = [SweepJob("bad", model.params, bad)] + [
        SweepJob(f"g{i}", model.params, good) for i in range(4)
    ]
    before = threading.active_count()
    with pytest.raises(ValueError, match="empty trace"):
        sweeper.run(jobs)
    # the producer thread (if any) wound down instead of leaking
    for _ in range(50):
        if threading.active_count() <= before:
            break
        import time

        time.sleep(0.05)
    assert threading.active_count() <= before
