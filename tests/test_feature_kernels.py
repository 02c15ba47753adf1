"""Device feature extraction (the fused megakernel) vs the jnp scan
oracles and the NumPy executable specification.

The contract is EXACT (bitwise) equivalence: branch-history rows move only
{-1, 0, +1} values, memory-distance deltas are exact int32 subtractions,
and the signed-log compression runs as a jax twin of
``core.features.signed_log`` (both sides a fixed chain of individually
rounded float32 ops, eagerly or compiled behind rounding barriers).
Covers feature configs and grid chunks of their own (``tests/test_fused.py``
holds one config), hash-collision-heavy traces (many PCs per bucket),
empty-queue boundaries, chunk-boundary geometry, and the int32
address-window refusal.
"""
import jax
import numpy as np
import pytest

from repro.core.features import (
    FeatureConfig,
    FeatureSet,
    extract_features,
    extract_features_reference,
    signed_log,
)
from repro.kernels.fused.ops import (
    ADDR_EXACT_LIMIT,
    DEFAULT_CHUNK,
    FusedExtractor,
    signed_log_device,
    trace_columns,
)
from repro.kernels.fused.ref import (
    branch_history_scan_ref,
    memdist_delta_scan_ref,
)
from repro.uarch import get_benchmark, run_functional
from repro.uarch.isa import FUNC_TRACE_DTYPE, Op

FIELDS = ("opcode", "regbits", "flags", "brhist", "memdist")


def _fused_columns(cols, cfg, chunk=DEFAULT_CHUNK):
    """One whole-trace fused pass over host columns (one batch)."""
    return FusedExtractor(cols, cfg, chunk=chunk).next_batch(len(cols["bucket"]))


def _fused_features(trace, cfg, chunk=DEFAULT_CHUNK):
    """The fused extraction of a whole trace as a host FeatureSet."""
    b = _fused_columns(trace_columns(trace, cfg), cfg, chunk)
    return FeatureSet(**{f: np.asarray(b[f]) for f in FIELDS})


def _scan_columns(n, bucket=None, outcome=None, addr=None, mem=None):
    """Raw fused-kernel columns from scan-oracle inputs: ``outcome`` in
    {-1, 0, +1} becomes the branch/taken bits, ``mem`` the mem bit."""
    z = np.zeros(n, np.int32)
    outcome = np.zeros(n, np.float32) if outcome is None else outcome
    mem = z if mem is None else mem
    return {
        "bucket": z if bucket is None else bucket,
        "addr": z if addr is None else addr,
        "opcode": z, "dst": z, "src1": z, "src2": z,
        "is_branch": outcome != 0.0,
        "taken": outcome > 0.0,
        "is_mem": mem != 0,
        "is_store": np.zeros(n, bool),
    }


def _assert_featuresets_bitwise(a, b, msg=""):
    for f in FIELDS:
        np.testing.assert_array_equal(
            getattr(a, f), getattr(b, f), err_msg=f"{msg}/{f}"
        )


def _random_trace(n, rng, branch_p=0.4, mem_p=0.4, pc_mod=64, addr_hi=1 << 20):
    t = np.zeros(n, dtype=FUNC_TRACE_DTYPE)
    t["pc"] = rng.integers(0, pc_mod, n) * 4
    t["opcode"] = rng.integers(0, len(Op), n)
    t["dst"] = rng.integers(0, 32, n)
    t["src1"] = rng.integers(0, 32, n)
    t["src2"] = rng.integers(0, 32, n)
    t["is_branch"] = rng.random(n) < branch_p
    t["taken"] = rng.random(n) < 0.5
    t["is_mem"] = (rng.random(n) < mem_p) & ~t["is_branch"]
    t["is_store"] = t["is_mem"] & (rng.random(n) < 0.5)
    t["addr"] = np.where(t["is_mem"], rng.integers(0, addr_hi, n), 0)
    return t


# ---------------------------------------------------------------------------
# signed-log determinism
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["eager", "jit"])
def test_signed_log_numpy_jax_bitwise_identical(mode):
    """The NumPy spec and its jax twin agree bit for bit, op per dispatch
    and compiled (where only the rounding barriers keep XLA from
    contracting each Horner step into an fma)."""
    rng = np.random.default_rng(7)
    d = np.concatenate(
        [
            np.arange(-4096, 4096),
            rng.integers(-(2**24), 2**24, 100_000),
            rng.integers(-(2**31) + 1, 2**31 - 1, 50_000),
            [0, 1, -1, 2**24, -(2**24), 2**31 - 100],
        ]
    ).astype(np.float32)
    a = signed_log(d)
    if mode == "jit":
        b = np.asarray(jax.jit(signed_log_device)(d, np.int32(0)))
    else:
        b = np.asarray(signed_log_device(d, np.int32(0)))
    np.testing.assert_array_equal(a.view(np.int32), b.view(np.int32))


def test_signed_log_accuracy_vs_true_log2():
    rng = np.random.default_rng(8)
    d = rng.integers(1, 2**24, 20_000).astype(np.float64)
    got = signed_log(d).astype(np.float64)
    want = np.log2(1.0 + d) / 32.0
    np.testing.assert_allclose(got, want, rtol=2e-7, atol=0)


# ---------------------------------------------------------------------------
# the megakernel's scans vs the jnp scan oracles (padding / chunk geometry)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,chunk", [(64, 64), (100, 32), (7, 32), (515, 128)])
def test_branch_history_kernel_vs_scan_ref(n, chunk):
    rng = np.random.default_rng(n * 31 + chunk)
    n_buckets, n_queue = 8, 5
    bucket = rng.integers(0, n_buckets, n).astype(np.int32)
    outcome = rng.choice([-1.0, 0.0, 1.0], n).astype(np.float32)
    cfg = FeatureConfig(n_buckets=n_buckets, n_queue=n_queue, n_mem=4)
    ker = _fused_columns(
        _scan_columns(n, bucket=bucket, outcome=outcome), cfg, chunk
    )["brhist"]
    ref = branch_history_scan_ref(
        bucket, outcome, n_buckets=n_buckets, n_queue=n_queue
    )
    np.testing.assert_array_equal(np.asarray(ker), np.asarray(ref))


@pytest.mark.parametrize("n,chunk", [(64, 64), (100, 32), (7, 32), (515, 128)])
def test_memdist_kernel_vs_scan_ref(n, chunk):
    rng = np.random.default_rng(n * 37 + chunk)
    n_mem = 6
    addr = rng.integers(0, 1 << 20, n).astype(np.int32)
    mem = (rng.random(n) < 0.6).astype(np.int32)
    cfg = FeatureConfig(n_buckets=4, n_queue=3, n_mem=n_mem)
    ker = _fused_columns(_scan_columns(n, addr=addr, mem=mem), cfg, chunk)["memdist"]
    ref = signed_log_device(memdist_delta_scan_ref(addr, mem, n_mem=n_mem), np.int32(0))
    np.testing.assert_array_equal(
        np.asarray(ker).view(np.int32), np.asarray(ref).view(np.int32)
    )


def test_kernels_empty_input():
    b = _fused_columns(_scan_columns(0), FeatureConfig(n_buckets=4, n_queue=3, n_mem=4))
    assert b["brhist"].shape == (0, 3)
    assert b["memdist"].shape == (0, 4)


# ---------------------------------------------------------------------------
# device extraction vs the NumPy executable specification (bitwise)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bench", ["mcf", "dee", "lee"])
def test_device_extraction_matches_reference_bitwise(bench):
    ft = run_functional(get_benchmark(bench), 2500)
    for cfg in (
        FeatureConfig(n_buckets=32, n_queue=4, n_mem=8),
        FeatureConfig(n_buckets=2, n_queue=3, n_mem=2),
    ):
        ref = extract_features_reference(ft, cfg, with_labels=False)
        dev = _fused_features(ft, cfg, chunk=256)
        _assert_featuresets_bitwise(ref, dev, msg=f"{bench}/{cfg.n_buckets}")


def test_device_extraction_hash_collision_heavy():
    """Many distinct PCs folded into very few buckets (paper Fig 4's
    deliberate aliasing) — the device table must mix histories exactly as
    the per-branch interpreter loop does."""
    rng = np.random.default_rng(3)
    t = _random_trace(4000, rng, branch_p=0.8, mem_p=0.15, pc_mod=512)
    for cfg in (
        FeatureConfig(n_buckets=1, n_queue=4, n_mem=4),
        FeatureConfig(n_buckets=2, n_queue=8, n_mem=4),
        FeatureConfig(n_buckets=3, n_queue=5, n_mem=4),  # non-power-of-two
    ):
        ref = extract_features_reference(t, cfg, with_labels=False)
        dev = _fused_features(t, cfg, chunk=512)
        _assert_featuresets_bitwise(ref, dev, msg=f"nb={cfg.n_buckets}")


def test_device_extraction_empty_queue_boundaries():
    """First-branch / first-access rows see empty queues; traces with no
    branches or no memory ops at all stay all-zero."""
    cfg = FeatureConfig(n_buckets=4, n_queue=3, n_mem=3)
    rng = np.random.default_rng(5)
    cases = {
        "no_branches": _random_trace(300, rng, branch_p=0.0, mem_p=0.5),
        "no_mem": _random_trace(300, rng, branch_p=0.5, mem_p=0.0),
        "neither": _random_trace(300, rng, branch_p=0.0, mem_p=0.0),
        "single": _random_trace(1, rng),
        "pair": _random_trace(2, rng),
    }
    for name, t in cases.items():
        ref = extract_features_reference(t, cfg, with_labels=False)
        dev = _fused_features(t, cfg, chunk=64)
        _assert_featuresets_bitwise(ref, dev, msg=name)
    assert not _fused_features(cases["neither"], cfg).brhist.any()


def test_device_extraction_matches_vectorized_bitwise():
    """All three implementations (reference loop, vectorized NumPy, fused)
    agree bitwise on a mem-heavy trace with negative/zero/duplicate deltas."""
    rng = np.random.default_rng(11)
    t = _random_trace(2000, rng, branch_p=0.3, mem_p=0.7, addr_hi=1 << 24)
    cfg = FeatureConfig(n_buckets=16, n_queue=6, n_mem=12)
    ref = extract_features_reference(t, cfg, with_labels=False)
    vec = extract_features(t, cfg, with_labels=False)
    dev = _fused_features(t, cfg)
    _assert_featuresets_bitwise(ref, vec, msg="vec")
    _assert_featuresets_bitwise(ref, dev, msg="dev")


def test_trace_columns_rejects_wide_addresses():
    t = _random_trace(16, np.random.default_rng(0))
    t["addr"][3] = ADDR_EXACT_LIMIT  # exactly at the limit -> reject
    with pytest.raises(ValueError, match="2\\^30"):
        trace_columns(t, FeatureConfig())
    with pytest.raises(ValueError):
        _fused_features(t, FeatureConfig())

