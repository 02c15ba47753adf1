"""Compile-only guards for the TPU: the main path's kernels and jitted
simulate step, compiled for a described ``v5e:2x2`` topology without a chip.

Interpret mode cannot see what Mosaic refuses (block shapes off the (8, 128)
tiling, scalars read out of vectors, unaligned lane shifts), nor a step that
does not fit the device.  These compile the fused megakernel at the
published widths (``repro.configs.tao``) and at the CI geometry, and the
fp32 simulate step at the published widths on one chip and under a 4-chip
data plan, for the full batch and for the half-batch tail — a few seconds
each, no chip time.  The topology is described inside a
fixture (never at import), so every xdist worker collects the same tests and
only the worker running this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding
from repro.configs.tao import CONFIG
from repro.core import FeatureConfig
from repro.core.model import init_tao
from repro.engine import EngineConfig, ExecutionPlan, StreamingEngine, clear_step_cache
from repro.engine.aot import abstract_like
from repro.kernels.fused.ops import (
    _COLUMN_KEYS,
    DEFAULT_CHUNK,
    _fused_padded,
    init_fused_state,
)

CI_FEATURES = FeatureConfig(n_buckets=32, n_queue=4, n_mem=8)
PUBLISHED = CONFIG.features
BATCH = 64


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip write cache entries no CPU process can
    # read back: keep the persistent cache out of it
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    """ShapeDtypeStructs of ``tree`` placed on the described device."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        abstract_like(tree),
    )


@pytest.mark.parametrize(
    "fc,window", [(PUBLISHED, CONFIG.window), (CI_FEATURES, 17)],
    ids=["published", "ci"],
)
def test_fused_megakernel_compiles(one_chip, fc, window):
    n = BATCH * window
    packed = jax.ShapeDtypeStruct((len(_COLUMN_KEYS), n), jnp.int32)
    counts = jax.ShapeDtypeStruct((2,), jnp.int32)
    state = jax.eval_shape(lambda: init_fused_state(fc))
    lowered = _fused_padded.lower(
        *_on(one_chip, [packed, state["table"], state["queue"], counts]),
        shape=(BATCH, window), n_queue=fc.n_queue, n_mem=fc.n_mem, n_flags=fc.flags_dim,
        chunk=DEFAULT_CHUNK, interpret=False,
    )
    assert "tpu_custom_call" in lowered.as_text()
    lowered.compile()


@pytest.mark.parametrize("chips", [1, 4])
def test_simulate_step_compiles_at_published_widths(topo, chips):
    """The engine's jitted fp32 step (``tao_forward`` + metric fold) for one
    64 x 129 batch, lowered from ``jax.eval_shape`` shapes: on one chip,
    and under a 4-chip data ExecutionPlan (shard_map + psum)."""
    params = jax.eval_shape(lambda: init_tao(jax.random.PRNGKey(0), CONFIG))
    if chips == 1:
        plan = None
        whole = rows = SingleDeviceSharding(topo.devices[0])
    else:
        mesh = Mesh(np.array(topo.devices[:4]).reshape(4), ("data",))
        plan = ExecutionPlan.resolve(mesh, batch_size=BATCH)
        whole = NamedSharding(mesh, PartitionSpec())
        rows = plan.batch_sharding()
    engine = StreamingEngine(params, CONFIG, EngineConfig(batch_size=BATCH, plan=plan))
    n = 100_000
    try:
        compiled = engine.step_entries_for(n)[0].fn.lower(
            _on(whole, params),
            _on(whole, jax.eval_shape(lambda: engine.init_carry(n))),
            _on(rows, engine._abstract_batch(BATCH, CONFIG.window)),
        ).compile()
    finally:
        # the process-wide step entry now holds a trace for the described
        # device; later CPU engines of this geometry must start clean
        clear_step_cache()
    mem = compiled.memory_analysis()
    # the chip holds 16 GB; the step needs ~0.1 GB of arguments + temporaries
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 2 * 2**30


def test_stacked_sweep_step_compiles_at_published_widths(topo):
    """The design-space sweep's stacked step (``heads=32``: 32 models of the
    published widths over one shared 64 x 129 batch) under the 4-chip data
    plan, as the ``dse32.sweep-4chip`` cell runs it."""
    from repro.engine.scheduler import stack_params

    heads = 32
    params = jax.eval_shape(
        lambda: stack_params([init_tao(jax.random.PRNGKey(i), CONFIG) for i in range(heads)])
    )
    mesh = Mesh(np.array(topo.devices[:4]).reshape(4), ("data",))
    plan = ExecutionPlan.resolve(mesh, batch_size=BATCH)
    whole = NamedSharding(mesh, PartitionSpec())
    engine = StreamingEngine(
        params, CONFIG, EngineConfig(batch_size=BATCH, plan=plan), heads=heads
    )
    n = 131_072
    try:
        compiled = engine.step_entries_for(n)[0].fn.lower(
            _on(whole, params),
            _on(whole, jax.eval_shape(lambda: engine.init_carry(n))),
            _on(plan.batch_sharding(), engine._abstract_batch(BATCH, CONFIG.window)),
        ).compile()
    finally:
        clear_step_cache()
    mem = compiled.memory_analysis()
    # per chip: the 32 stacked models (2.5 GB) and the step's temporaries,
    # beside the 32 models the sweep holds replicated (2.5 GB), of 16 GB
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 6 * 2**30


@pytest.mark.parametrize("chips", [1, 4])
def test_half_batch_tail_compiles_at_published_widths(topo, chips):
    """The half-batch tail geometry (32 x 129) that a request ends on when
    at most 32 windows are left: its step on one chip and under the 4-chip
    data plan (8 rows per chip), and on one chip its extraction program."""
    params = jax.eval_shape(lambda: init_tao(jax.random.PRNGKey(0), CONFIG))
    whole = rows = SingleDeviceSharding(topo.devices[0])
    plan = None
    if chips == 4:
        mesh = Mesh(np.array(topo.devices[:4]).reshape(4), ("data",))
        plan = ExecutionPlan.resolve(mesh, batch_size=BATCH)
        whole = NamedSharding(mesh, PartitionSpec())
        rows = plan.batch_sharding()
    engine = StreamingEngine(params, CONFIG, EngineConfig(batch_size=BATCH, plan=plan))
    n = (BATCH + 5) * CONFIG.window
    assert engine.batch_rows(n) == (BATCH, BATCH // 2)
    try:
        _, half = engine.step_entries_for(n)
        half.fn.lower(
            _on(whole, params),
            _on(whole, jax.eval_shape(lambda: engine.init_carry(n))),
            _on(rows, engine._abstract_batch(BATCH // 2, CONFIG.window)),
        ).compile()
    finally:
        clear_step_cache()
    if chips == 1:
        state = jax.eval_shape(lambda: init_fused_state(PUBLISHED))
        m = BATCH // 2 * CONFIG.window
        _fused_padded.lower(
            *_on(whole, [jax.ShapeDtypeStruct((len(_COLUMN_KEYS), m), jnp.int32),
                         state["table"], state["queue"],
                         jax.ShapeDtypeStruct((2,), jnp.int32)]),
            shape=(BATCH // 2, CONFIG.window), n_queue=PUBLISHED.n_queue,
            n_mem=PUBLISHED.n_mem, n_flags=PUBLISHED.flags_dim,
            chunk=DEFAULT_CHUNK, interpret=False,
        ).compile()


def test_simnet_scan_compiles_at_published_widths(one_chip):
    """SimNet's sequential scan (``engine/sequential.py``) at the C3 widths
    for one launch of 1,024 sub-traces (warm-up 128 + 1,024 iterations), as
    the ``simnet-c3.sim-long`` cell runs it, and its smallest tail."""
    from repro.core.simnet import SimNetConfig, init_simnet
    from repro.engine import SimNetEngine

    cfg = SimNetConfig()
    params = jax.eval_shape(lambda: init_simnet(jax.random.PRNGKey(0), cfg))
    engine = SimNetEngine(params, cfg, EngineConfig(batch_size=1024))
    n = 1024 * cfg.subtrace
    assert engine.batch_rows(n) == (1024,)
    assert engine.batch_rows(1) == (64,)
    try:
        for rows in (1024, 64):
            compiled = engine._get_step((rows, cfg.window)).fn.lower(
                _on(one_chip, params), {}, _on(one_chip, engine._abstract_batch(rows, cfg.window)),
            ).compile()
            mem = compiled.memory_analysis()
            # the packed input (9 MB), the latencies out (9 MB), the carry
            # and one iteration's activations (~0.3 GB at 1,024 rows)
            assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 2 * 2**30
    finally:
        clear_step_cache()
