"""§4.3/§5.5 Transfer learning to an unseen microarchitecture.

Three regimes (paper Table 5):
  * scratch              — full model trained from random init
  * direct fine-tuning   — all parameters initialized from a donor model
  * shared + fine-tune   — Tao's scheme: µarch-agnostic embeddings FROZEN,
                           adaptation + prediction layers fine-tuned on a
                           small dataset (20M instructions in the paper)
"""
from __future__ import annotations

import dataclasses
import functools
import time
import warnings
from typing import Callable, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..spans import call_span, span
from ..train.optim import AdamWConfig, adamw_init, adamw_update
from ..train.trainer import CachedTrainStep, cached_train_step
from ..uarch.isa import NUM_REGS
from .dataset import StreamingWindowDataset, WindowDataset
from .model import TaoConfig, init_tao, multi_metric_loss, tao_forward

__all__ = [
    "TrainResult",
    "train_tao",
    "train_tao_impl",
    "transfer_finetune",
    "warmup_train_step",
]

# Both dataset flavors expose the same ``batches(batch_size, rng=...)``
# contract (bit-identical streams for the same rng); everything below is
# agnostic to which one it is handed.
TrainData = Union[WindowDataset, StreamingWindowDataset]


@dataclasses.dataclass
class TrainResult:
    params: Dict
    losses: List[float]
    eval_losses: List[float]
    seconds: float
    steps: int


# tao: step-builder[train-step]
def _make_step(cfg: TaoConfig, opt_cfg: AdamWConfig, trainable: str, plan=None):
    """trainable: 'all' or 'headonly' (freeze shared embeddings).

    The step is cached process-wide (``train.trainer.cached_train_step``):
    params and optimizer state are arguments, so every trainer invocation
    with the same (config, optimizer, trainable set, plan) shares one
    executable, and — because batches are fixed-shape — it traces exactly
    once per (batch, window) geometry.  ``plan`` (an ``ExecutionPlan``)
    only keys the cache here: the step itself stays a plain jit and GSPMD
    partitions it from the plan's input placements (batch sharded over
    the plan's axes, params/opt replicated), so a sharded and an
    unsharded trainer never share an executable under one trace counter."""

    def build(entry):
        def loss_fn(params, batch):
            preds = tao_forward(params, batch, cfg)
            loss, _ = multi_metric_loss(preds, batch["labels"])
            return loss

        if trainable == "all":

            @jax.jit
            def step(params, opt, batch):
                entry.compiles += 1  # runs at trace time only
                loss, grads = jax.value_and_grad(loss_fn)(params, batch)
                params, opt, _ = adamw_update(params, grads, opt, opt_cfg)
                return params, opt, loss

            return step

        @jax.jit
        def step(params, opt, batch):
            entry.compiles += 1  # runs at trace time only
            # Freeze the shared embedding group: grads only for adapt+pred.
            def loss_head(head_params, embed_params, batch):
                full = {"embed": embed_params, **head_params}
                return loss_fn(full, batch)

            head = {"adapt": params["adapt"], "pred": params["pred"]}
            loss, grads = jax.value_and_grad(loss_head)(head, params["embed"], batch)
            head, opt, _ = adamw_update(head, grads, opt, opt_cfg)
            return {"embed": params["embed"], **head}, opt, loss

        return step

    # the entry itself is callable (dispatching its AOT executable when
    # warmup_train_step has compiled one), so callers use it like the fn
    return cached_train_step(  # tao: step-key[train-step]
        ("tao", cfg, opt_cfg, trainable, plan), build
    )


def warmup_train_step(
    cfg: TaoConfig,
    *,
    batch_size: int = 16,
    lr: float = 3e-4,
    freeze_embed: bool = False,
    plan=None,
    window: Optional[int] = None,
) -> CachedTrainStep:
    """AOT-compile the cached train step for a training recipe ahead of
    any data: params/optimizer shapes come from ``jax.eval_shape`` over
    ``init_tao``, the batch from the dataset layer's declared geometry
    (``window`` defaults to ``cfg.window`` — pass the effective window for
    traces shorter than it).  Single-device only: on a sharded plan (or
    multi-process run) the entry is built but dispatch stays with the
    jitted step, whose first call the persistent compilation cache serves.
    Idempotent per (recipe, geometry)."""
    from ..engine.aot import abstract_like, compile_bytes_estimate

    if plan is not None and not plan.sharded:
        plan = None  # same normalization as train_tao_impl
    opt_cfg = AdamWConfig(lr=lr)
    trainable = "headonly" if freeze_embed else "all"
    entry = _make_step(cfg, opt_cfg, trainable, plan=plan)
    if entry.aot is not None:
        return entry
    if plan is not None or jax.process_count() > 1:
        return entry

    params = jax.eval_shape(
        functools.partial(init_tao, cfg=cfg), jax.random.PRNGKey(0)
    )
    if freeze_embed:
        opt = jax.eval_shape(
            adamw_init, {"adapt": params["adapt"], "pred": params["pred"]}
        )
    else:
        opt = jax.eval_shape(adamw_init, params)

    w = window if window is not None else cfg.window
    b = batch_size
    f = cfg.features
    sds = jax.ShapeDtypeStruct
    # the exact shapes/dtypes WindowDataset/StreamingWindowDataset batches
    # carry: INPUT_KEYS plus the label dict from features._labels
    batch = {
        "opcode": sds((b, w), jnp.int32),
        "regbits": sds((b, w, NUM_REGS), jnp.float32),
        "flags": sds((b, w, f.flags_dim), jnp.float32),
        "brhist": sds((b, w, f.n_queue), jnp.float32),
        "memdist": sds((b, w, f.n_mem), jnp.float32),
        "labels": {
            "fetch_lat": sds((b, w), jnp.float32),
            "exec_lat": sds((b, w), jnp.float32),
            "mispred": sds((b, w), jnp.float32),
            "dlevel": sds((b, w), jnp.int32),
            "icache_miss": sds((b, w), jnp.float32),
            "tlb_miss": sds((b, w), jnp.float32),
            "is_branch": sds((b, w), jnp.float32),
            "is_mem": sds((b, w), jnp.float32),
        },
    }
    compiled = entry.fn.lower(abstract_like(params), abstract_like(opt), batch).compile()
    entry.est_bytes = compile_bytes_estimate(compiled)
    entry.aot = compiled
    return entry


# tao: hot
def _run_epochs(
    params,
    step,
    dataset: TrainData,
    epochs: int,
    batch_size: int,
    opt,
    eval_fn: Optional[Callable] = None,
    seed: int = 0,
    target_loss: Optional[float] = None,
    prefetch: bool = True,
    plan=None,
    start_epoch: int = 0,
    rng_state: Optional[Dict] = None,
    losses: Optional[List[float]] = None,
    evals: Optional[List[float]] = None,
    steps: int = 0,
    checkpoint_cb: Optional[Callable] = None,
) -> Tuple[Dict, List[float], List[float], int]:
    # lazy: engine.runner imports core.dataset — a module-level import here
    # would close the cycle through the repro.core package init
    from ..engine.runner import prefetch_to_device

    if plan is not None and plan.sharded:
        # data-parallel training under the same ExecutionPlan the engine
        # uses: batches shard over the plan's batch axes (device_put
        # below), params/opt replicate, and GSPMD inserts the gradient
        # all-reduce.  The batch stream itself is untouched, so the
        # sampled windows match the single-device run exactly.
        plan.validate_batch(batch_size)
        with span("train.prepare"):
            params = plan.replicate(params)
            opt = plan.replicate(opt)

    rng = np.random.default_rng(seed)
    if rng_state is not None:
        # crash-resume: fast-forward the shuffle stream to where the
        # checkpointed epoch left it, so the remaining epochs draw exactly
        # the batches an uninterrupted run would have drawn
        rng.bit_generator.state = rng_state
    losses = list(losses) if losses else []
    evals = list(evals) if evals else []
    put = plan.device_put if plan is not None and plan.sharded else None
    for ep in range(start_epoch, epochs):
        nb = 0
        ep_losses: list = []
        batches = dataset.batches(batch_size, rng=rng)
        if prefetch:
            # double-buffered host→device transfer (and, on accelerator
            # backends, threaded batch gather) — numerics are unchanged:
            # the step sees the same arrays, just already device-resident
            batches = prefetch_to_device(batches, put)
        elif put is not None:
            batches = (put(b) for b in batches)
        for batch in batches:
            with span("train.step"):
                params, opt, loss = step(params, opt, batch)
            # keep the device scalar: a float() here would sync the
            # dispatch queue once per step and serialize the prefetch
            ep_losses.append(loss)
            nb += 1
            steps += 1
        # one explicit sync per epoch; summing the host scalars in step
        # order keeps the loss trajectory bit-identical to the old
        # per-step accumulation
        with span("train.epoch_sync"):
            ep_losses = jax.device_get(ep_losses)
            ep_loss = 0.0
            for x in ep_losses:
                ep_loss += float(x)  # tao: noqa[TAO002] host numpy scalar from the per-epoch device_get above, not a device sync
            ep_loss /= max(nb, 1)
        losses.append(ep_loss)
        if eval_fn is not None:
            evals.append(float(jax.device_get(eval_fn(params))))
        if checkpoint_cb is not None:
            # rng state captured AFTER this epoch's batches were drawn —
            # exactly what the next epoch of a resumed run must start from
            checkpoint_cb(
                ep, params, opt, losses, evals, steps,
                rng.bit_generator.state,
            )
        if target_loss is not None and ep_loss <= target_loss:
            break
    return params, losses, evals, steps


def train_tao_impl(
    cfg: TaoConfig,
    dataset: TrainData,
    *,
    epochs: int = 10,
    batch_size: int = 16,
    lr: float = 3e-4,
    init_params: Optional[Dict] = None,
    freeze_embed: bool = False,
    eval_fn: Optional[Callable] = None,
    seed: int = 0,
    target_loss: Optional[float] = None,
    plan=None,
    store=None,
    resume_key: Optional[str] = None,
    manifest_every: int = 1,
) -> TrainResult:
    """Train (or fine-tune) a single-µarch Tao model.

    scratch            -> init_params=None,  freeze_embed=False
    direct fine-tune   -> init_params=donor, freeze_embed=False
    shared + fine-tune -> init_params={'embed': shared, ...}, freeze_embed=True

    ``dataset`` may be a materialized ``WindowDataset`` or a
    ``StreamingWindowDataset`` (O(trace + batch) host memory); both produce
    bit-identical loss trajectories for the same seed and keep-set.

    ``plan`` (an ``repro.engine.ExecutionPlan``) runs the cached step
    data-parallel over the plan's mesh — same batch stream, batches
    sharded over the batch axes, params replicated, gradient all-reduce
    by GSPMD.  ``train_step_compiles`` still counts one trace per
    (batch, window) geometry per plan.

    With ``store`` (an ``ArtifactStore``) and ``resume_key`` (the run's
    recipe identity — ``Session.train`` passes its params content key),
    every ``manifest_every``-th epoch publishes a crash-resume manifest
    (params, optimizer state, loss history, shuffle-rng state) through the
    store; a re-run after a SIGKILL picks up from the last checkpointed
    epoch with zero redundant step executions, and its loss trajectory
    and final params are bit-identical to an uninterrupted run.

    Internal implementation behind ``repro.api.Session.train`` /
    ``TrainedModel.transfer`` (and the ``train_tao`` deprecation shim).
    """
    if manifest_every < 1:
        raise ValueError(f"manifest_every must be >= 1, got {manifest_every}")
    with call_span("train.run") as sp:
        # step lookup, optimizer state, resume: before the first step
        with span("train.prepare"):
            key = jax.random.PRNGKey(seed)
            params = init_params if init_params is not None else init_tao(key, cfg)
            opt_cfg = AdamWConfig(lr=lr)
            trainable = "headonly" if freeze_embed else "all"
            if plan is not None and not plan.sharded:
                # the single-device plan is the default path; normalizing to None
                # keeps one step-cache entry (and one compile) for both spellings
                plan = None
            step = _make_step(cfg, opt_cfg, trainable, plan=plan)
            if freeze_embed:
                opt = adamw_init({"adapt": params["adapt"], "pred": params["pred"]})
            else:
                opt = adamw_init(params)

            start_epoch, rng_state, steps0 = 0, None, 0
            losses0: List[float] = []
            evals0: List[float] = []
            checkpoint_cb = None
            if store is not None and resume_key is not None:
                # lazy: resilience.manifest pulls in the store package
                from ..resilience.manifest import load_train_epoch, publish_train_epoch

                state = load_train_epoch(store, resume_key, epochs)
                if state is not None and state.get("rng_state") is not None:
                    params = state["params"]
                    # stored as a plain dict (the typed-path serializer holds
                    # dict/list/tuple trees only) — rebuild the NamedTuple
                    opt = type(opt)(**state["opt"])
                    start_epoch = state["epoch"] + 1
                    rng_state = state["rng_state"]
                    losses0 = state["losses"]
                    evals0 = state["eval_losses"]
                    steps0 = state["steps"]

                def checkpoint_cb(ep, p, o, ls, ev, st, rs):
                    if (ep + 1) % manifest_every and ep != epochs - 1:
                        return
                    publish_train_epoch(
                        store, resume_key, ep, jax.device_get(p),
                        jax.device_get(o)._asdict(), ls, ev, st, rs,
                    )

        t0 = time.perf_counter()
        params, losses, evals, steps = _run_epochs(
            params, step, dataset, epochs, batch_size, opt, eval_fn, seed,
            target_loss, plan=plan, start_epoch=start_epoch, rng_state=rng_state,
            losses=losses0, evals=evals0, steps=steps0,
            checkpoint_cb=checkpoint_cb,
        )
        seconds = time.perf_counter() - t0
        # the steps this call ran (a resumed run's earlier ones excluded)
        sp.set_metadata(steps=steps - steps0, windows=(steps - steps0) * batch_size)
        return TrainResult(
            params=params,
            losses=losses,
            eval_losses=evals,
            seconds=seconds,
            steps=steps,
        )


def train_tao(cfg: TaoConfig, dataset: TrainData, **kw) -> TrainResult:
    """Deprecated alias for :func:`train_tao_impl` — use the
    ``repro.api`` facade instead (``Session.train`` / ``model.transfer``)."""
    warnings.warn(
        "repro.core.train_tao is deprecated; use repro.api.Session.train(...) "
        "(or TrainedModel.transfer for fine-tuning)",
        DeprecationWarning,
        stacklevel=2,
    )
    return train_tao_impl(cfg, dataset, **kw)


def transfer_finetune(
    cfg: TaoConfig,
    shared_embed: Dict,
    donor_arch_params: Dict,
    small_dataset: TrainData,
    **kw,
) -> TrainResult:
    """Tao's fast path: frozen shared embeddings + donor-initialized heads,
    fine-tuned on a reduced dataset."""
    init = {
        "embed": shared_embed,
        "adapt": donor_arch_params["adapt"],
        "pred": donor_arch_params["pred"],
    }
    return train_tao_impl(
        cfg, small_dataset, init_params=init, freeze_embed=True, **kw
    )
