"""§4.2 feature-engineering tests."""
import numpy as np
import pytest

from repro.core.features import FeatureConfig, extract_features
from repro.uarch.isa import FUNC_TRACE_DTYPE, NUM_REGS, Op


def _mk_trace(rows):
    t = np.zeros(len(rows), dtype=FUNC_TRACE_DTYPE)
    for i, r in enumerate(rows):
        for k, v in r.items():
            t[i][k] = v
    return t


def test_regbits_contains_sources_and_dest():
    t = _mk_trace([{"opcode": int(Op.IALU), "dst": 3, "src1": 5, "src2": 7}])
    fs = extract_features(t, FeatureConfig(), with_labels=False)
    bits = np.nonzero(fs.regbits[0])[0].tolist()
    assert set(bits) == {3, 5, 7}
    assert fs.regbits.shape == (1, NUM_REGS)


def test_opcode_passthrough_and_flags():
    t = _mk_trace(
        [
            {"opcode": int(Op.FMUL)},
            {"opcode": int(Op.LOAD), "is_mem": True, "addr": 64},
            {"opcode": int(Op.STORE), "is_mem": True, "is_store": True, "addr": 8},
            {"opcode": int(Op.BEQ), "is_branch": True, "taken": True},
        ]
    )
    fs = extract_features(t, FeatureConfig(), with_labels=False)
    assert fs.opcode.tolist() == [int(Op.FMUL), int(Op.LOAD), int(Op.STORE), int(Op.BEQ)]
    assert fs.flags[0, 4] == 1.0            # is_fp
    assert fs.flags[1, 2] == 1.0            # is_mem
    assert fs.flags[2, 3] == 1.0            # is_store
    assert fs.flags[3, 0] == 1.0 and fs.flags[3, 1] == 1.0  # branch, taken


def test_branch_history_hash_table():
    cfg = FeatureConfig(n_buckets=4, n_queue=3)
    pc = 16  # bucket (16>>2) % 4 == 0
    rows = [
        {"opcode": int(Op.BEQ), "pc": pc, "is_branch": True, "taken": True},
        {"opcode": int(Op.BEQ), "pc": pc, "is_branch": True, "taken": False},
        {"opcode": int(Op.BEQ), "pc": pc, "is_branch": True, "taken": True},
    ]
    fs = extract_features(_mk_trace(rows), cfg, with_labels=False)
    # first branch: empty history
    assert fs.brhist[0].tolist() == [0.0, 0.0, 0.0]
    # second: sees [taken] = [+1]
    assert fs.brhist[1].tolist() == [1.0, 0.0, 0.0]
    # third: most-recent-first [not-taken, taken]
    assert fs.brhist[2].tolist() == [-1.0, 1.0, 0.0]


def test_branch_hash_collision_mixes_histories():
    """Two different PCs in the same bucket share a queue (paper Fig 4)."""
    cfg = FeatureConfig(n_buckets=2, n_queue=2)
    pc_a, pc_b = 0, 8  # (0>>2)%2 == (8>>2)%2 == 0
    rows = [
        {"opcode": int(Op.BEQ), "pc": pc_a, "is_branch": True, "taken": True},
        {"opcode": int(Op.BEQ), "pc": pc_b, "is_branch": True, "taken": False},
    ]
    fs = extract_features(_mk_trace(rows), cfg, with_labels=False)
    assert fs.brhist[1].tolist() == [1.0, 0.0]  # sees pc_a's outcome


def test_memdist_signed_log_deltas():
    cfg = FeatureConfig(n_mem=2)
    rows = [
        {"opcode": int(Op.LOAD), "is_mem": True, "addr": 100},
        {"opcode": int(Op.LOAD), "is_mem": True, "addr": 108},
        {"opcode": int(Op.LOAD), "is_mem": True, "addr": 100},
    ]
    fs = extract_features(_mk_trace(rows), cfg, with_labels=False)
    assert fs.memdist[0].tolist() == [0.0, 0.0]          # first access: empty
    d1 = fs.memdist[1]
    assert d1[0] == pytest.approx(np.log2(1 + 8) / 32.0)  # +8 delta
    d2 = fs.memdist[2]
    assert d2[0] == pytest.approx(-np.log2(1 + 8) / 32.0)  # -8 (most recent)
    assert d2[1] == pytest.approx(0.0)                     # same addr as [0]


def test_nonbranch_nonmem_rows_zero():
    t = _mk_trace([{"opcode": int(Op.IALU)}])
    fs = extract_features(t, FeatureConfig(), with_labels=False)
    assert not fs.brhist[0].any()
    assert not fs.memdist[0].any()


def test_feature_backends_bitwise_identical_on_unit_traces():
    """NumPy and fused backends agree bit for bit on the hand-built unit
    traces above (collisions, empty queues, signed deltas included)."""
    from repro.kernels.fused.ops import FusedExtractor, trace_columns

    cfg = FeatureConfig(n_buckets=2, n_queue=3, n_mem=2)
    rows = [
        {"opcode": int(Op.BEQ), "pc": 0, "is_branch": True, "taken": True},
        {"opcode": int(Op.BEQ), "pc": 8, "is_branch": True, "taken": False},
        {"opcode": int(Op.LOAD), "is_mem": True, "addr": 100},
        {"opcode": int(Op.LOAD), "is_mem": True, "addr": 108},
        {"opcode": int(Op.STORE), "is_mem": True, "is_store": True, "addr": 100},
        {"opcode": int(Op.FMUL), "dst": 3, "src1": 5, "src2": 7},
        {"opcode": int(Op.BEQ), "pc": 16, "is_branch": True, "taken": True},
    ]
    t = _mk_trace(rows)
    host = extract_features(t, cfg, with_labels=False)
    dev = FusedExtractor(trace_columns(t, cfg), cfg, chunk=4).next_batch(len(t))
    for f in ("opcode", "regbits", "flags", "brhist", "memdist"):
        np.testing.assert_array_equal(getattr(host, f), np.asarray(dev[f]), err_msg=f)


def test_labels_from_adjusted_trace(small_tao_setup):
    _, ds, al, _ = small_tao_setup
    assert ds.labels is not None
    assert set(ds.labels) >= {"fetch_lat", "exec_lat", "mispred", "dlevel"}
    assert (ds.labels["fetch_lat"] >= 0).all()
    assert (ds.labels["dlevel"] <= 3).all()
