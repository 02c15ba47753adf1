"""Content-addressed artifact store + AOT/persistent-cache tests.

Covers the zero-cold-start invariant end to end: content identity
(``repro.store.content``), the template-free typed-path checkpoint format
it serializes through, the store's atomicity/corruption/GC behavior, the
digest-keyed sweep dedup, the engine/trainer step-cache stats + AOT
``warmup`` paths, Session store plumbing — and, in a subprocess pair, the
cross-process guarantee: a second process re-running a previously-seen
sweep against a warm store performs **0 XLA compiles and 0 feature
extractions** and reproduces every metric bit for bit.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.api import ArtifactStore, Session, Trace
from repro.ckpt import load_array_tree, save_array_tree
from repro.core import FeatureConfig, TaoConfig
from repro.core.features import extract_features
from repro.core.model import init_tao
from repro.core.transfer import train_tao_impl, warmup_train_step
from repro.engine import EngineConfig, StreamingEngine, cache_stats, clear_step_cache
from repro.engine.scheduler import SweepJob, TraceSweeper
from repro.store import array_digest, config_token, content_key, tree_digest
from repro.train.trainer import cache_stats as train_cache_stats
from repro.train.trainer import clear_train_step_cache
from repro.uarch import UARCH_A, get_benchmark, run_functional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = TaoConfig(
    window=9, d_model=16, n_heads=2, n_layers=1, d_ff=32, d_cat=8,
    features=FeatureConfig(n_buckets=64, n_queue=4, n_mem=8),
)


@pytest.fixture(scope="module")
def trace():
    return run_functional(get_benchmark("dee"), 1200)


@pytest.fixture(scope="module")
def params():
    return init_tao(jax.random.PRNGKey(0), CFG)


# ---------------------------------------------------------------------------
# Content identity
# ---------------------------------------------------------------------------


def test_array_digest_content_not_identity(trace):
    other = trace.copy()  # distinct object, equal content
    assert other is not trace
    assert array_digest(other) == array_digest(trace)
    mutated = trace.copy()
    mutated["opcode"][0] += 1
    assert array_digest(mutated) != array_digest(trace)


def test_array_digest_dtype_and_shape_sensitive():
    a = np.zeros(8, np.int32)
    assert array_digest(a) != array_digest(a.astype(np.float32))
    assert array_digest(a) != array_digest(a.reshape(2, 4))
    # non-contiguous views digest by content, not memory layout
    b = np.arange(16, dtype=np.int32)
    assert array_digest(b[::2]) == array_digest(np.ascontiguousarray(b[::2]))


def test_tree_digest_structure_sensitive():
    x = np.arange(4.0)
    assert tree_digest({"a": x, "b": x}) != tree_digest({"a": x, "c": x})
    assert tree_digest([x, x]) != tree_digest([x])
    assert tree_digest({"a": {"b": x}}) != tree_digest({"a": {"c": x}})


def test_config_token_and_content_key_stability():
    t1 = config_token(CFG)
    t2 = config_token(
        TaoConfig(window=9, d_model=16, n_heads=2, n_layers=1, d_ff=32,
                  d_cat=8, features=FeatureConfig(n_buckets=64, n_queue=4, n_mem=8))
    )
    assert t1 == t2
    assert content_key("params", t1) == content_key("params", t2)
    # kind namespaces the key
    assert content_key("params", t1) != content_key("features", t1)
    with pytest.raises(TypeError):
        config_token(object())


def test_trace_and_featureset_digest(trace):
    tr = Trace(name="t", functional=trace, program=get_benchmark("dee"))
    assert tr.digest == array_digest(trace)
    fs = extract_features(trace, CFG.features, with_labels=False)
    fs2 = extract_features(trace.copy(), CFG.features, with_labels=False)
    assert fs.digest == fs2.digest
    assert fs.digest == fs.digest  # cached property path


# ---------------------------------------------------------------------------
# Typed-path checkpoint format (template-free restore)
# ---------------------------------------------------------------------------


def test_array_tree_roundtrip_nested_and_list(tmp_path):
    tree = {
        "embed": {"w": np.arange(12.0, dtype=np.float32).reshape(3, 4)},
        "blocks": [
            {"k": np.ones((2, 2), np.float32)},
            {"k": np.zeros((2, 2), np.float32)},
        ],
        "scalar": np.float32(3.5),
    }
    save_array_tree(tree, str(tmp_path / "e"), extra={"note": "hi"})
    got, extra = load_array_tree(str(tmp_path / "e"))
    assert extra == {"note": "hi"}
    assert isinstance(got["blocks"], list) and len(got["blocks"]) == 2
    np.testing.assert_array_equal(got["embed"]["w"], tree["embed"]["w"])
    np.testing.assert_array_equal(got["blocks"][1]["k"], tree["blocks"][1]["k"])
    assert got["scalar"] == np.float32(3.5)


def test_array_tree_roundtrip_structured_and_bf16(tmp_path, trace):
    import jax.numpy as jnp

    tree = {"trace": trace, "bf": np.arange(6, dtype=np.dtype(jnp.bfloat16))}
    save_array_tree(tree, str(tmp_path / "e"))
    got, _ = load_array_tree(str(tmp_path / "e"))
    np.testing.assert_array_equal(got["trace"], trace)
    assert got["bf"].dtype == np.dtype(jnp.bfloat16)
    np.testing.assert_array_equal(
        got["bf"].astype(np.float32), tree["bf"].astype(np.float32)
    )


def test_array_tree_truncation_detected(tmp_path):
    save_array_tree({"w": np.arange(100.0)}, str(tmp_path / "e"))
    # truncate the payload: load must fail loudly, not return garbage
    for name in os.listdir(tmp_path / "e"):
        if name.endswith(".bin"):
            p = tmp_path / "e" / name
            with open(p, "r+b") as f:
                f.truncate(10)
    with pytest.raises(ValueError, match="truncated"):
        load_array_tree(str(tmp_path / "e"))


# ---------------------------------------------------------------------------
# ArtifactStore: atomicity, corruption-as-miss, GC
# ---------------------------------------------------------------------------


def test_store_roundtrip_and_counters(tmp_path):
    st = ArtifactStore(str(tmp_path / "s"))
    key = content_key("features", "abc")
    assert st.get("features", key) is None          # miss
    assert st.put("features", key, {"x": np.arange(3.0)}, {"n": 3})
    assert not st.put("features", key, {"x": np.arange(3.0)})  # immutable
    assert st.has("features", key)
    tree, extra = st.get("features", key)
    np.testing.assert_array_equal(tree["x"], np.arange(3.0))
    assert extra == {"n": 3}
    s = st.stats()
    assert s["entries"] == 1 and s["hits"] == 1 and s["misses"] == 1
    assert s["puts"] == 1 and s["bytes"] > 0


def test_store_corruption_quarantined(tmp_path):
    st = ArtifactStore(str(tmp_path / "s"))
    key = content_key("params", "k")
    st.put("params", key, {"w": np.arange(50.0)})
    edir = st._entry_dir("params", key)
    for name in os.listdir(edir):
        if name.endswith(".bin"):
            with open(os.path.join(edir, name), "r+b") as f:
                f.truncate(4)
    assert st.get("params", key) is None            # corrupt -> miss
    assert st.counters["corrupt_dropped"] == 1
    assert not st.has("params", key)                # quarantined (deleted)
    # recompute-and-reput works
    assert st.put("params", key, {"w": np.arange(50.0)})
    assert st.get("params", key) is not None


def test_store_gc_budget_and_age(tmp_path):
    st = ArtifactStore(str(tmp_path / "s"))
    for i in range(4):
        st.put("features", content_key("features", i), {"x": np.arange(100.0)})
    assert st.stats()["entries"] == 4
    out = st.gc(max_bytes=st.stats()["bytes"] // 2)
    assert out["evicted"] >= 1
    assert st.stats()["entries"] < 4
    st.gc(max_age_s=0.0)                            # everything is "old"
    assert st.stats()["entries"] == 0
    # stale staging dirs are swept, fresh ones are left alone
    os.makedirs(os.path.join(st.root, "tmp", "torn-123-1"))
    os.utime(os.path.join(st.root, "tmp", "torn-123-1"), (0, 0))
    st.gc()
    assert not os.path.exists(os.path.join(st.root, "tmp", "torn-123-1"))


def test_store_self_gc_with_max_bytes(tmp_path):
    st = ArtifactStore(str(tmp_path / "s"), max_bytes=1)
    st.put("features", content_key("features", 1), {"x": np.arange(100.0)})
    st.put("features", content_key("features", 2), {"x": np.arange(100.0)})
    assert st.stats()["entries"] <= 1               # each put GCs to budget


# ---------------------------------------------------------------------------
# Pinning: readers block GC (the serving regression)
# ---------------------------------------------------------------------------


def test_store_pin_blocks_gc_same_host(tmp_path):
    st = ArtifactStore(str(tmp_path / "s"))
    k1, k2 = content_key("features", 1), content_key("features", 2)
    st.put("features", k1, {"x": np.arange(10.0)})
    st.put("features", k2, {"x": np.arange(10.0) + 1})
    other = ArtifactStore(str(tmp_path / "s"))      # GC from "elsewhere"
    with st.pin("features", k1) as pinned:
        assert pinned
        other.gc(max_age_s=0.0)
        assert st.has("features", k1)               # pinned entry survives
        assert not st.has("features", k2)           # unpinned is collected
        assert other.counters["gc_pin_skips"] == 1
        # byte-budget pass also skips the pinned entry
        other.gc(max_bytes=0)
        assert st.has("features", k1)
    other.gc(max_age_s=0.0)                         # pin released
    assert not st.has("features", k1)
    # explicit delete is an operator decision: it ignores pins
    st.put("features", k1, {"x": np.arange(10.0)})
    with st.pin("features", k1):
        assert st.delete("features", k1)
    assert not st.has("features", k1)


def test_store_pin_missing_entry_and_stale_pid(tmp_path):
    st = ArtifactStore(str(tmp_path / "s"))
    # pinning a never-published entry reports pinned=False (caller treats
    # it as an ordinary miss and recomputes)
    with st.pin("features", content_key("features", "never")) as pinned:
        assert not pinned
    # a stale marker from a dead pid must not block GC forever
    k = content_key("features", "x")
    st.put("features", k, {"x": np.arange(3.0)})
    edir = st._entry_dir("features", k)
    open(os.path.join(edir, ".pin-999999999-1"), "x").close()
    st.gc(max_age_s=0.0)
    assert not st.has("features", k)
    assert st.counters["gc_pin_skips"] == 0


_PIN_CHILD = r"""
import sys
from repro.api import ArtifactStore
st = ArtifactStore(sys.argv[1])
with st.pin(sys.argv[2], sys.argv[3]) as pinned:
    print("PINNED" if pinned else "MISSING", flush=True)
    sys.stdin.readline()                  # hold the pin until released
print("DONE", flush=True)
"""


def test_store_pin_cross_process(tmp_path):
    """A serving process streaming an entry pins it; GC in this process
    must skip it until the reader exits (ISSUE satellite regression)."""
    root = str(tmp_path / "s")
    st = ArtifactStore(root)
    k = content_key("serve_model", "served")
    st.put("serve_model", k, {"w": np.arange(20.0)})
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
    )
    p = subprocess.Popen(
        [sys.executable, "-c", _PIN_CHILD, root, "serve_model", k],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
        env=env, cwd=ROOT,
    )
    try:
        assert p.stdout.readline().strip() == "PINNED"
        st.gc(max_age_s=0.0)
        assert st.has("serve_model", k)             # reader keeps it alive
        assert st.counters["gc_pin_skips"] == 1
    finally:
        p.stdin.write("\n")
        p.stdin.flush()
        assert p.wait(timeout=120) == 0
    st.gc(max_age_s=0.0)
    assert not st.has("serve_model", k)


# ---------------------------------------------------------------------------
# Step-cache stats + AOT warmup (engine and trainer)
# ---------------------------------------------------------------------------


def test_engine_cache_stats_and_clear(trace, params):
    clear_step_cache()
    e1 = StreamingEngine(params, CFG, EngineConfig(batch_size=8))
    r1 = e1.simulate(trace)
    s = cache_stats()
    assert s["entries"] >= 1 and s["misses"] >= 1
    hits0 = s["hits"]
    e2 = StreamingEngine(params, CFG, EngineConfig(batch_size=8))
    r2 = e2.simulate(trace)                          # same geometry -> hit
    assert cache_stats()["hits"] > hits0
    assert r2.cpi == r1.cpi
    assert clear_step_cache() >= 1
    assert cache_stats()["entries"] == 0


def test_engine_warmup_aot_bit_identical(trace, params):
    ecfg = EngineConfig(batch_size=8)
    lazy = StreamingEngine(params, CFG, ecfg).simulate(trace)
    clear_step_cache()
    eng = StreamingEngine(params, CFG, ecfg)
    entry = eng.warmup(len(trace))
    if jax.process_count() == 1:
        assert entry.aot is not None                 # AOT path active
        assert cache_stats()["aot_compiled"] >= 1
    res = eng.simulate(trace)
    assert res.cpi == lazy.cpi
    assert res.branch_mpki == lazy.branch_mpki
    assert res.l1d_mpki == lazy.l1d_mpki


def test_train_warmup_aot_bit_identical():
    s = Session(CFG, batch_size=8)
    tr = s.capture("dee", 900)
    ds = s.dataset(UARCH_A, [tr])
    lazy = train_tao_impl(CFG, ds, epochs=2, batch_size=8, lr=1e-3, seed=0)
    clear_train_step_cache()
    entry = warmup_train_step(CFG, batch_size=8, lr=1e-3)
    assert entry.aot is not None
    ts = train_cache_stats()
    assert ts["entries"] == 1 and ts["aot_compiled"] == 1
    warm = train_tao_impl(CFG, ds, epochs=2, batch_size=8, lr=1e-3, seed=0)
    assert warm.losses == lazy.losses                # bit-identical through AOT
    # the warmed entry was reused, not rebuilt
    assert train_cache_stats()["hits"] >= 1


# ---------------------------------------------------------------------------
# Digest-keyed sweep dedup + store-backed feature prep
# ---------------------------------------------------------------------------


def test_sweep_digest_dedup_and_store(tmp_path, trace, params):
    st = ArtifactStore(str(tmp_path / "s"))
    jobs = [
        SweepJob("m/a", params, trace),
        SweepJob("m/b", params, trace.copy()),       # equal content, new object
    ]
    rep = TraceSweeper(CFG, EngineConfig(batch_size=8), store=st).run(jobs)
    # content-digest dedup: one extraction serves both jobs
    assert rep.features_extracted == 1
    assert rep.features_from_store == 0
    assert rep.results["m/a"].cpi == rep.results["m/b"].cpi
    # a second sweeper over the same store extracts nothing
    rep2 = TraceSweeper(CFG, EngineConfig(batch_size=8), store=st).run(jobs)
    assert rep2.features_extracted == 0
    assert rep2.features_from_store == 1
    assert rep2.results["m/a"].cpi == rep.results["m/a"].cpi
    assert rep2.stats()["features_from_store"] == 1


# ---------------------------------------------------------------------------
# Session store plumbing (same-process reuse)
# ---------------------------------------------------------------------------


def test_session_store_reuse(tmp_path):
    root = str(tmp_path / "store")
    s1 = Session(CFG, batch_size=8, store=root, compile_cache=False)
    tr1 = s1.capture("dee", 900)
    gt1 = s1.ground_truth(UARCH_A, tr1)
    m1 = s1.train(UARCH_A, [tr1], epochs=1, batch_size=8)
    r1 = m1.simulate(tr1)

    s2 = Session(CFG, batch_size=8, store=root, compile_cache=False)
    tr2 = s2.capture("dee", 900)
    np.testing.assert_array_equal(tr2.functional, tr1.functional)
    assert s2.ground_truth(UARCH_A, tr2) == gt1
    m2 = s2.train(UARCH_A, [tr2], epochs=1, batch_size=8)
    for a, b in zip(jax.tree.leaves(m1.params), jax.tree.leaves(m2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert m2.losses == m1.losses
    assert m2.simulate(tr2).cpi == r1.cpi
    st = s2.store.stats()
    assert st["misses"] == 0 and st["puts"] == 0, st  # fully warm
    assert st["hits"] >= 4


def test_session_train_key_sensitivity(tmp_path):
    """Different recipes must not collide in the params cache."""
    root = str(tmp_path / "store")
    s = Session(CFG, batch_size=8, store=root, compile_cache=False)
    tr = s.capture("dee", 900)
    m1 = s.train(UARCH_A, [tr], epochs=1, batch_size=8)
    m2 = s.train(UARCH_A, [tr], epochs=2, batch_size=8)   # new recipe
    assert m2.steps > m1.steps
    m3 = s.train(UARCH_A, [tr], epochs=1, batch_size=8)   # hit (in-session)
    for a, b in zip(jax.tree.leaves(m1.params), jax.tree.leaves(m3.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Cross-process zero-cold-start (the acceptance test)
# ---------------------------------------------------------------------------

_CHILD = r"""
import json, sys
import numpy as np
from repro.api import Session
from repro.core import FeatureConfig, TaoConfig
from repro.core.features import num_extractions
from repro.engine import xla_cache_counters

cfg = TaoConfig(
    window=9, d_model=16, n_heads=2, n_layers=1, d_ff=32, d_cat=8,
    features=FeatureConfig(n_buckets=64, n_queue=4, n_mem=8),
)
METRICS = ("cpi", "branch_mpki", "l1d_mpki", "cpi_phase")
sess = Session(cfg, batch_size=8, store=sys.argv[1])
tr = sess.capture("dee", 1200)
model = sess.init_model(seed=3)
rep = sess.sweep({"m": model}, {"t": tr}, metrics=METRICS)
res = rep.results["m/t"]
fu = model.simulate(tr, feature_backend="fused", metrics=METRICS)
print("CHILD:" + json.dumps({
    "cpi": res.cpi,
    "branch_mpki": res.branch_mpki,
    "l1d_mpki": res.l1d_mpki,
    "cpi_phase": np.asarray(res.cpi_phase).tolist(),
    "fused_cpi": fu.cpi,
    "fused_branch_mpki": fu.branch_mpki,
    "fused_l1d_mpki": fu.l1d_mpki,
    "fused_cpi_phase": np.asarray(fu.cpi_phase).tolist(),
    "xla": xla_cache_counters(),
    "extractions": num_extractions(),
    "sweep_extracted": rep.features_extracted,
    "sweep_from_store": rep.features_from_store,
}))
"""


def _run_child(store_dir: str, cache_dir: str) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # subprocess must never probe TPU
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
    )
    p = subprocess.run(
        [sys.executable, "-c", _CHILD, store_dir],
        capture_output=True, text=True, timeout=1200, env=env, cwd=ROOT,
    )
    assert p.returncode == 0, p.stderr[-3000:]
    line = [ln for ln in p.stdout.splitlines() if ln.startswith("CHILD:")][-1]
    return json.loads(line[len("CHILD:"):])


def test_cross_process_zero_cold_start(tmp_path):
    """Second process, warm store + persistent compilation cache: 0 XLA
    compiles, 0 host feature extractions, bit-identical CPI / MPKI /
    phase-curve results on both feature backends."""
    store = str(tmp_path / "store")
    cache = str(tmp_path / "jax")
    cold = _run_child(store, cache)
    warm = _run_child(store, cache)

    # cold process did real work and persisted it
    assert cold["xla"]["misses"] > 0
    assert cold["extractions"] >= 1

    # warm process: every compile request served from disk, zero XLA
    assert warm["xla"]["requests"] > 0
    assert warm["xla"]["misses"] == 0, warm["xla"]
    assert warm["xla"]["hits"] == warm["xla"]["requests"]
    # zero host feature extraction (sweep + simulate both hit the store)
    assert warm["extractions"] == 0
    assert warm["sweep_extracted"] == 0
    assert warm["sweep_from_store"] == 1

    # bit-identical results, scalar and phase curve, on both backends
    for k in (
        "cpi", "branch_mpki", "l1d_mpki", "cpi_phase",
        "fused_cpi", "fused_branch_mpki", "fused_l1d_mpki",
        "fused_cpi_phase",
    ):
        assert warm[k] == cold[k], k


_CACHE_CHILD = r"""
import json, os, sys
import jax, jax.numpy as jnp
from repro.api import Session
Session(compile_cache=True)
jax.jit(lambda x: x * 3.0 + 1.0)(jnp.arange(11.0)).block_until_ready()
print("CHILD:" + json.dumps({"dir": jax.config.jax_compilation_cache_dir}))
"""


def _cache_child(cache_dir):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    p = subprocess.run(
        [sys.executable, "-c", _CACHE_CHILD],
        capture_output=True, text=True, timeout=300, env=env, cwd=ROOT,
    )
    assert p.returncode == 0, p.stderr[-3000:]
    line = [ln for ln in p.stdout.splitlines() if ln.startswith("CHILD:")][-1]
    return json.loads(line[len("CHILD:"):])["dir"]


def test_compile_cache_placed_from_outside(tmp_path):
    """$JAX_COMPILATION_CACHE_DIR is the cache when set — executables land
    there and nowhere else; unset, the cache is the fixed, git-ignored
    <checkout>/.cache/jax."""
    from repro.engine.aot import DEFAULT_CACHE_DIR

    def listing(d):
        return set(os.listdir(d)) if os.path.isdir(d) else set()

    outside = str(tmp_path / "jax")
    before = listing(DEFAULT_CACHE_DIR)
    assert _cache_child(outside) == outside
    assert any(f.endswith("-cache") for f in os.listdir(outside))
    assert listing(DEFAULT_CACHE_DIR) == before

    assert os.path.normpath(DEFAULT_CACHE_DIR) == os.path.normpath(
        os.path.join(ROOT, ".cache", "jax")
    )
    assert _cache_child(None) == DEFAULT_CACHE_DIR
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".cache/" in f.read().splitlines()
