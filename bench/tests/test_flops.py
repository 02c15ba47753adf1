"""Operations and bytes from shapes, against the published configuration."""
import json
import os

import jax
import pytest

from bench import flops
from bench.reference import model as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def config(name="tao-paper"):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        return json.load(f)


def test_config_file_is_the_published_config():
    from repro.configs.tao import CONFIG

    w = config()
    assert (w["window"], w["d_model"], w["n_heads"], w["n_layers"], w["d_ff"], w["d_cat"]) == (
        CONFIG.window, CONFIG.d_model, CONFIG.n_heads, CONFIG.n_layers, CONFIG.d_ff, CONFIG.d_cat)
    f = CONFIG.features
    assert (w["n_buckets"], w["n_queue"], w["n_mem"], w["flags_dim"]) == (
        f.n_buckets, f.n_queue, f.n_mem, f.flags_dim)


def test_forward_flops_and_kernel_bytes_per_instruction():
    w = config()
    # 6 blocks x (3,145,728 matmul + 132,096 attention) MACs + 626,816 MACs
    # of embedding, adapt and heads = 20,293,760 MACs
    assert flops.forward_flops_per_instruction(w) == 2 * 20_293_760
    assert flops.forward_flops_per_instruction(w) / 1e6 == pytest.approx(40.6, abs=0.05)
    assert flops.kernel_bytes_per_instruction(w) == {"read": 48, "write": 532}
    # 64 windows x 129 positions per engine step: ~335 GFLOP
    assert flops.step_cost(w)["flops"] == pytest.approx(335.09e9, rel=1e-4)
    assert flops.train_flops_per_window(w) / 1e9 == pytest.approx(15.46, abs=0.01)


def test_param_bytes_and_tree_match_the_program():
    from repro.configs.tao import CONFIG
    from repro.core.model import init_tao

    w = config()
    prog = jax.eval_shape(lambda k: init_tao(k, CONFIG), jax.random.PRNGKey(0))
    mine = jax.eval_shape(lambda k: ref.init_params(k, w), jax.random.PRNGKey(0))
    assert jax.tree.structure(prog) == jax.tree.structure(mine)
    assert [(x.shape, x.dtype) for x in jax.tree.leaves(prog)] == [
        (x.shape, x.dtype) for x in jax.tree.leaves(mine)]
    assert flops.param_bytes(w) == 4 * sum(x.size for x in jax.tree.leaves(prog))
    assert flops.embed_bytes(w) == 4 * sum(x.size for x in jax.tree.leaves(prog["embed"]))


def test_roofline_is_the_larger_bound():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.least_seconds(200.0, 10.0, peak) == 2.0
    assert flops.least_seconds(100.0, 50.0, peak) == 5.0
