"""Multi-device integration tests (subprocess with 8 placeholder devices):
sharded training runs, elastic restart across mesh shapes, and one real
dry-run cell end to end."""
import os
import subprocess
import sys
import textwrap


ROOT = os.path.join(os.path.dirname(__file__), "..")
SRC = os.path.join(ROOT, "src")


def _run(script: str, timeout=560) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    # Force CPU: --xla_force_host_platform_device_count works with it, and
    # the test process may already hold the TPU runtime, which a child on
    # the same chip cannot open.
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
    )
    assert p.returncode == 0, f"stdout:\n{p.stdout}\nstderr:\n{p.stderr[-4000:]}"
    return p.stdout


def test_sharded_train_and_elastic_restart(tmp_path):
    script = textwrap.dedent(f"""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_arch
    from repro.models.backbone import Model
    from repro.train.trainer import TrainConfig, init_state, make_train_step, state_axes, batch_axes
    from repro.launch.mesh import make_mesh
    from repro.launch.dryrun import _shardings_for
    from repro.distributed.sharding import mesh_context
    from repro.ckpt import CheckpointManager
    from repro.data.pipeline import LMDataPipeline

    cfg = get_arch("qwen2-0.5b", reduced=True)
    model = Model(cfg)
    tcfg = TrainConfig(lr=1e-3, total_steps=6, warmup_steps=1)
    pipe = LMDataPipeline(cfg, batch=8, seq=32, seed=0)

    def train_on(mesh_shape, axes, state, steps, start):
        mesh = make_mesh(mesh_shape, axes)
        with mesh_context(mesh):
            s_ax = state_axes(model)
            st_sh = _shardings_for(s_ax, jax.eval_shape(lambda: state), mesh)
            step = jax.jit(make_train_step(model, tcfg),
                           in_shardings=(st_sh, None), out_shardings=(st_sh, None))
            state = jax.device_put(state, st_sh)
            m = None
            for i in range(start, start + steps):
                state, m = step(state, jax.tree.map(jnp.asarray, pipe.make_batch(i)))
            return jax.tree.map(lambda x: np.asarray(x), state), float(m["loss"])

    state = init_state(model, jax.random.PRNGKey(0), tcfg)
    state = jax.tree.map(lambda x: np.asarray(x), state)
    state, l1 = train_on((2, 2), ("data", "model"), state, 3, 0)
    mgr = CheckpointManager(r"{tmp_path}", use_async=False)
    mgr.save(state, 3)

    # elastic restart: restore the same checkpoint into a DIFFERENT mesh
    restored, extra = mgr.restore_latest(state)
    state2, l2 = train_on((4, 2), ("data", "model"), restored, 3, 3)
    assert np.isfinite(l2)
    print("LOSSES", l1, l2)
    """)
    out = _run(script)
    assert "LOSSES" in out


def test_dryrun_cell_end_to_end():
    """Smallest real cell through run_cell (512-device mesh, AOT compile)."""
    script = textwrap.dedent("""
    from repro.launch.dryrun import run_cell
    rec = run_cell("qwen2-0.5b", "decode_32k", multi_pod=False)
    assert rec["memory"]["fits_16gb"], rec["memory"]
    assert rec["roofline"]["dominant"] in ("compute_s", "memory_s", "collective_s")
    assert rec["flops_per_device"] > 0
    print("CELL_OK", rec["roofline"]["dominant"])
    """)
    out = _run(script)
    assert "CELL_OK" in out


def test_multipod_mesh_builds_and_shards():
    script = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    import jax, jax.numpy as jnp
    from repro.launch.mesh import make_production_mesh
    from repro.distributed.sharding import mesh_context, logical_to_spec
    mesh = make_production_mesh(multi_pod=True)
    assert mesh.devices.size == 512
    assert mesh.shape == {"pod": 2, "data": 16, "model": 16}
    spec = logical_to_spec(("batch", None), shape=(256, 64), mesh=mesh)
    assert spec == jax.sharding.PartitionSpec(("pod", "data"))
    print("MESH_OK")
    """)
    out = _run(script)
    assert "MESH_OK" in out
