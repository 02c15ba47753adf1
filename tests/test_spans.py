"""The program's profiler spans (``repro.spans``) and the executable names
the benchmark's trace readers match.

A simulation and a transfer call are profiled with ``jax.profiler.trace``
and read back with ``ProfileData``: every span of the two hot paths is
there, children nest in their call's span and carry its ``call``, and the
simulate span's arguments count what the engine did.  The executables'
module names are pinned, so a rename fails here instead of silently
leaving a benchmark metric without events.
"""
import glob
import os
import re

import jax
import numpy as np
import pytest

from repro.api import Session, TrainedModel
from repro.core import FeatureConfig, TaoConfig, init_tao
from repro.core.transfer import warmup_train_step
from repro.engine import EngineConfig, StreamingEngine
from repro.engine.runner import prefetch_to_device
from repro.kernels.fused.ops import (
    _fused_padded,
    _pack,
    init_fused_state,
    kernel_chunk,
    trace_columns,
)
from repro.spans import call_span
from repro.uarch import UARCH_A
from repro.uarch.isa import FUNC_TRACE_DTYPE

# widths of this file alone, so its step-cache entries are its own
FCFG = FeatureConfig(n_buckets=16, n_queue=4, n_mem=4)
CFG = TaoConfig(window=9, d_model=16, n_heads=2, n_layers=1, d_ff=32, d_cat=8, features=FCFG)
BATCH = 8
N = 1000

SIM_SPANS = ("engine.simulate", "engine.columns", "engine.upload", "fused.extract",
             "engine.step", "engine.sync")
TRAIN_SPANS = ("train.run", "train.prepare", "feed.gather", "feed.put", "feed.wait",
               "train.step", "train.epoch_sync")


def tao_events(trace_dir):
    """``(start_ns, end_ns, name, args, line)`` of every ``tao/`` event
    (``line``: the host thread's line in the trace)."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("tao/"):
                    out.append((e.start_ns, e.end_ns, e.name[4:], dict(e.stats), (plane.name, i)))
    return sorted(out)


def named(evs, name):
    return [e for e in evs if e[2] == name]


def assert_nested(evs, parent):
    """Every other span lies inside the one ``parent`` span and shares its
    call."""
    (top,) = named(evs, parent)
    for s, e, name, args, _ in evs:
        assert top[0] <= s and e <= top[1], name
        assert args["call"] == top[3]["call"], name
    return top


@pytest.fixture(scope="module")
def sess():
    return Session(CFG)


@pytest.fixture(scope="module")
def trace(sess):
    return sess.capture("mcf", N)


@pytest.fixture(scope="module")
def params():
    return init_tao(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def engine(params):
    return StreamingEngine(params, CFG, EngineConfig(batch_size=BATCH, feature_backend="fused"))


def test_simulate_spans(engine, trace, tmp_path):
    engine.simulate(trace.functional)  # compile outside the profile
    with jax.profiler.trace(str(tmp_path)):
        res = engine.simulate(trace.functional)
    evs = tao_events(str(tmp_path))
    assert {e[2] for e in evs} == set(SIM_SPANS)
    top = assert_nested(evs, "engine.simulate")
    args = top[3]
    w_eff = CFG.window
    nb = -(-(res.num_instructions // w_eff) // BATCH)
    assert args["instructions"] == res.num_instructions
    assert args["batches"] == nb
    assert args["positions"] == nb * BATCH * w_eff
    assert len(named(evs, "fused.extract")) == nb
    assert len(named(evs, "engine.step")) == nb
    assert len(named(evs, "engine.columns")) == len(named(evs, "engine.sync")) == 1
    # the request's initial state: the engine's kept zeros and one put
    assert len(named(evs, "engine.upload")) == 1
    # each step dispatch follows its batch's extraction
    for x, st in zip(named(evs, "fused.extract"), named(evs, "engine.step")):
        assert x[1] <= st[0]


def test_calls_get_their_own_identifier(engine, trace, tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        engine.simulate(trace.functional[:500])
        engine.simulate(trace.functional[:400])
    evs = tao_events(str(tmp_path))
    calls = [e[3]["call"] for e in named(evs, "engine.simulate")]
    assert len(set(calls)) == 2 and 0 not in calls
    for s, e, name, args, _ in evs:
        (owner,) = [c for c in named(evs, "engine.simulate") if c[0] <= s and e <= c[1]]
        assert args["call"] == owner[3]["call"], name


def test_transfer_spans(sess, trace, params, tmp_path):
    ds = sess.dataset(UARCH_A, trace).subsample(4 * BATCH)
    base = TrainedModel(params=params, cfg=CFG)
    base.transfer(ds, epochs=1, batch_size=BATCH)  # compile outside the profile
    with jax.profiler.trace(str(tmp_path)):
        tr = base.transfer(ds, epochs=2, batch_size=BATCH)
    evs = tao_events(str(tmp_path))
    assert {e[2] for e in evs} == set(TRAIN_SPANS)
    top = assert_nested(evs, "train.run")
    assert top[3]["steps"] == tr.steps == 8
    assert top[3]["windows"] == tr.steps * BATCH
    assert len(named(evs, "train.step")) == tr.steps
    assert len(named(evs, "feed.gather")) == len(named(evs, "feed.put")) == tr.steps
    assert len(named(evs, "train.epoch_sync")) == 2
    assert len(named(evs, "train.prepare")) == 1


def test_prefetch_thread_spans_keep_the_call(tmp_path):
    host = ({"x": np.full(4, i, np.float32)} for i in range(3))
    with jax.profiler.trace(str(tmp_path)):
        with call_span("train.run"):
            got = [float(b["x"][0]) for b in prefetch_to_device(host, threaded=True)]
    assert got == [0.0, 1.0, 2.0]
    evs = tao_events(str(tmp_path))
    puts = named(evs, "feed.put")
    assert len(puts) == 3
    # the producer thread's spans name the consumer's call
    (top,) = named(evs, "train.run")
    assert {p[3]["call"] for p in puts} == {top[3]["call"]}
    assert {p[4] for p in puts} != {top[4]}  # on another thread
    assert len(named(evs, "feed.wait")) == 4  # three batches, then the end


def module_name(compiled) -> str:
    return re.search(r"HloModule (\S+?),", compiled.as_text()).group(1)


def test_executable_names_the_trace_readers_match(engine, params):
    """The benchmark's readers match "XLA Modules" events by these names
    (``bench/metrics``): ``jit_body`` (engine step), ``jit__fused_padded``
    (fused extraction), ``jit_step`` (transfer step)."""
    entry = engine.warmup(N)
    assert module_name(entry.aot) == "jit_body"

    cols = trace_columns(np.zeros(64, dtype=FUNC_TRACE_DTYPE), FCFG)
    state = init_fused_state(FCFG)
    fused = _fused_padded.lower(
        _pack(cols, 0, 64), state["table"], state["queue"], np.array([64, 0], np.int32),
        shape=(BATCH, 8), n_queue=FCFG.n_queue, n_mem=FCFG.n_mem, n_flags=FCFG.flags_dim,
        chunk=kernel_chunk(64), interpret=True).compile()
    assert module_name(fused) == "jit__fused_padded"

    step = warmup_train_step(CFG, batch_size=BATCH, freeze_embed=True)
    assert module_name(step.aot) == "jit_step"
