"""Roofline machinery: the loop-aware HLO analyzer is validated against
XLA's own cost_analysis on loop-free graphs, and trip-count folding is
checked scanned-vs-unrolled."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch.hloanalysis import analyze_hlo


def _compile(fn, *sds):
    return jax.jit(fn).lower(*sds).compile()




def test_dot_flops_matches_cost_analysis_loop_free():
    def f(a, b, c):
        return (a @ b) @ c

    sds = [
        jax.ShapeDtypeStruct((64, 128), jnp.float32),
        jax.ShapeDtypeStruct((128, 256), jnp.float32),
        jax.ShapeDtypeStruct((256, 32), jnp.float32),
    ]
    c = _compile(f, *sds)
    ours = analyze_hlo(c.as_text())["dot_flops"]
    xla = c.cost_analysis()["flops"]
    assert ours == pytest.approx(xla, rel=0.05), (ours, xla)


def test_scan_trip_count_folding():
    """flops(scan of N matmuls) must be ~N x flops(one matmul)."""
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 64), jnp.float32)
    N = 12

    def one(x_, w_):
        return x_ @ w_

    def scanned(x_, w_):
        def body(c, _):
            return c @ w_, None

        c, _ = jax.lax.scan(body, x_, None, length=N)
        return c

    c1 = _compile(one, x, w)
    cN = _compile(scanned, x, w)
    f1 = analyze_hlo(c1.as_text())["dot_flops"]
    fN = analyze_hlo(cN.as_text())["dot_flops"]
    assert fN == pytest.approx(N * f1, rel=0.05), (f1, fN)
    # and confirm XLA's own analysis UNDER-counts the scan (the reason this
    # module exists) — if XLA ever fixes this, we can drop the custom parse
    xla_fN = cN.cost_analysis()["flops"]
    assert xla_fN < fN * 0.5


def test_collectives_counted_inside_loops():
    import os
    import subprocess
    import sys
    import textwrap

    script = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.launch.hloanalysis import analyze_hlo
    from repro.compat import make_mesh
    mesh = make_mesh((8,), ("model",))
    with jax.set_mesh(mesh):
        def f(w, x):
            def body(c, _):
                y = c @ w                      # contraction over sharded dim
                y = jax.lax.with_sharding_constraint(y, P(None, "model"))
                return y, None
            c, _ = jax.lax.scan(body, x, None, length=10)
            return c.sum()
        wsds = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        xsds = jax.ShapeDtypeStruct((8, 64), jnp.float32)
        c = jax.jit(f, in_shardings=(
            jax.sharding.NamedSharding(mesh, P("model", None)),
            jax.sharding.NamedSharding(mesh, P(None, "model")),
        )).lower(wsds, xsds).compile()
        h = analyze_hlo(c.as_text())
        counts = sum(v["count"] for v in h["collectives"].values())
        assert counts >= 10, h["collectives"]   # one per loop iteration
        print("COLL_OK", counts)
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"  # placeholder devices; avoid TPU probing
    p = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=300, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    assert "COLL_OK" in p.stdout


def test_analytic_flops_sane_for_dense_arch():
    """Analytic counter vs 6·N·D: same order, analytic >= forward share."""
    from repro.configs import get_arch
    from repro.launch.roofline import analytic_flops

    cfg = get_arch("glm4-9b")
    meta = {"batch": 256, "seq": 4096, "kind": "train"}
    af = analytic_flops(cfg, meta)
    # ~9.4B params (w/o embeddings) * 6 * 1M tokens
    n_eff = 9.0e9
    six_nd = 6 * n_eff * 256 * 4096
    assert 0.5 * six_nd < af < 4 * six_nd, (af, six_nd)
