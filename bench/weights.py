"""Weights made on the device from the run's seed, in one jitted call, in
float32 (the type they are served in), with the plain reference's
initialisation (``bench/reference/model.py``)."""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax

from bench.harness import derive_seed
from bench.reference import model as ref


def single(run) -> Dict:
    w = run.config
    key = jax.random.PRNGKey(derive_seed(run.seed, "weights"))
    return jax.jit(lambda k: ref.init_params(k, w))(key)


def shared(run, count: int) -> Tuple[Dict, List[Dict]]:
    """One embedding and ``count`` per-design (adapt, pred) groups."""
    w = run.config
    key = jax.random.PRNGKey(derive_seed(run.seed, "weights"))

    def make(k):
        ke, kh = jax.random.split(k)
        return ref.init_embed(ke, w), [ref.init_head(x, w) for x in jax.random.split(kh, count)]

    return jax.jit(make)(key)
