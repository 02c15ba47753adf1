"""Async multi-trace sweep scheduler (design-space exploration fast path).

A design-space sweep simulates a few functional traces on many design
points whose models share one shape (§4.3: per-design adapt+pred heads
over one µarch-agnostic embedding).  The scheduler makes the trace the
unit of work and the design points an axis of it:

    sweeper = TraceSweeper(cfg, EngineConfig(batch_size=64))
    report = sweeper.run([
        SweepJob("l1d16/mcf", params_16, trace_mcf),
        SweepJob("l1d16/xal", params_16, trace_xal),
        SweepJob("l1d32/mcf", params_32, trace_mcf),
        ...
    ])
    report.results["l1d16/mcf"].l1d_mpki
    report.num_compiles        # == 1 per effective-window geometry
    report.heads_per_step      # design points each step ran over one batch
    report.extractions         # device extraction programs: traces x batches

**Trace-major.**  Jobs are grouped by trace (the same trace array).  Each
group runs as ONE ``StreamingEngine.simulate`` over the stacked params of
every model with a job on that trace (``StreamingEngine(heads=K)``): the
trace's columns and its per-batch extraction program run once per
(trace, batch) on the fused backend, its host features once per distinct
trace content on the NumPy backend, and one step per batch runs every
head.  A group of one model keeps the one-model step.  The stacked tree
is built once per model set and kept by the sweeper (``stacks_built``),
placed as the plan places params, so repeated sweeps over the same
models restack nothing.

**Double buffering.**  A producer thread prepares groups into a bounded
queue (``depth`` slots — 2 = classic double buffering), overlapping the
host-side work of trace i+1 with the device execution of trace i.  On
CPU-only backends the producer thread would contend with the step's own
compute for the same cores, so preparation runs inline there
(``async_prepare`` overrides).  Every step comes from the process-wide
step cache, so the whole sweep compiles once per (window geometry, head
count) no matter how many (model, trace) pairs it covers.
"""
from __future__ import annotations

import dataclasses
import functools
import queue
import threading
import time
import weakref
from typing import Dict, Iterable, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dataset import num_windows
from ..core.features import FeatureSet, extract_features
from ..core.model import TaoConfig
from ..resilience.faults import fault_point
from ..spans import call_span, span
from ..store.content import array_digest, config_token, content_key, tree_digest
from .metrics import resolve_metrics
from .plan import ExecutionPlan
from .runner import EngineConfig, SimulationResult, StreamingEngine

__all__ = ["SweepJob", "SweepReport", "TraceSweeper", "stack_params", "sweep_traces"]


@dataclasses.dataclass(frozen=True)
class SweepJob:
    """One (model, trace) pair of a sweep."""

    key: str                 # e.g. "l1d32KB/mcf"
    params: Dict             # model parameters (same TaoConfig shape)
    trace: np.ndarray        # functional trace (FUNC_TRACE_DTYPE)


@dataclasses.dataclass
class SweepReport:
    """Results plus the scheduler's own performance counters."""

    results: Dict[str, SimulationResult]
    seconds: float           # wall clock for the whole sweep
    num_traces: int
    num_instructions: int
    # step compilations performed DURING this sweep (at most 1 per window
    # geometry; 0 when an earlier run already warmed the shared step cache)
    num_compiles: int
    traces_per_s: float
    mips: float              # aggregate instructions/s over the sweep wall clock
    queue_occupancy_mean: float  # prepared jobs waiting when the consumer polls
    queue_occupancy_max: int
    queue_depth: int
    prepared_async: bool = False  # threaded producer (False = inline on CPU)
    plan_kind: str = "single"     # ExecutionPlan kind the sweep ran under
    num_shards: int = 1           # devices each step fanned out over
    # host feature pre-passes this sweep actually ran vs loaded from the
    # artifact store (0 extracted on a warm store = the zero-cold-start
    # invariant; both stay 0 on the fused backend, which extracts on
    # device per batch)
    features_extracted: int = 0
    features_from_store: int = 0
    # jobs satisfied from crash-resume progress manifests (store entries
    # published by an earlier, possibly killed, run with the same
    # resume_key) — skipped entirely: no extraction, no device work
    jobs_skipped: int = 0
    # design points each step ran over one batch, averaged over the steps
    # (the models stacked per trace; 1 for a one-model sweep)
    heads_per_step: float = 0.0
    # device extraction programs launched (fused: one per (trace, batch);
    # numpy: none, see features_extracted)
    extractions: int = 0
    # stacked params trees built by this sweep (0 when the sweeper's kept
    # stack served every trace)
    stacks_built: int = 0

    def stats(self) -> Dict[str, Union[float, int, str]]:
        return {
            "traces_per_s": self.traces_per_s,
            "mips": self.mips,
            "num_compiles": self.num_compiles,
            "queue_occupancy_mean": self.queue_occupancy_mean,
            "queue_occupancy_max": self.queue_occupancy_max,
            "plan_kind": self.plan_kind,
            "num_shards": self.num_shards,
            "features_extracted": self.features_extracted,
            "features_from_store": self.features_from_store,
            "jobs_skipped": self.jobs_skipped,
            "heads_per_step": self.heads_per_step,
            "extractions": self.extractions,
            "stacks_built": self.stacks_built,
        }

    def to_dict(self) -> Dict:
        """Stable JSON-clean form: scheduler counters plus every result's
        ``SimulationResult.to_dict()`` — what the serve/bench layers
        serialize instead of reaching into report internals."""
        return {
            "seconds": self.seconds,
            "num_traces": self.num_traces,
            "num_instructions": self.num_instructions,
            "queue_depth": self.queue_depth,
            "prepared_async": self.prepared_async,
            **self.stats(),
            "results": {k: r.to_dict() for k, r in self.results.items()},
        }


_STOP = object()


_stack_leaf = jax.jit(jnp.stack)


def stack_params(trees: Sequence[Dict]) -> Dict:
    """K params trees of one shape -> one tree whose leaves carry a leading
    axis of K models (what ``StreamingEngine(heads=K)`` runs).  One
    compiled program per distinct leaf shape (the published model has 20):
    a single program over every leaf of 32 models (3,136 inputs) takes
    about a minute to compile on a 4-chip v5e host, and eager stacking
    compiles several programs per shape."""
    return jax.tree.map(lambda *xs: _stack_leaf(xs), *trees)


def abstract_params(cfg: TaoConfig, heads: int = 1) -> Dict:
    """The shapes of a model's params (``heads`` > 1: of ``heads`` models
    stacked), with nothing computed: what AOT warm-up lowers from."""
    from ..core.model import init_tao

    one = jax.eval_shape(functools.partial(init_tao, cfg=cfg), jax.random.PRNGKey(0))
    return one if heads == 1 else jax.eval_shape(stack_params, [one] * heads)


def _leaf_refs(trees: Sequence[Dict]) -> List:
    """Weak references to every leaf (held strongly where a leaf takes
    none), so a kept stack can tell its models are still the same objects
    without keeping them alive."""
    refs = []
    for leaf in jax.tree_util.tree_leaves(trees):
        try:
            refs.append(weakref.ref(leaf))
        except TypeError:
            refs.append(lambda leaf=leaf: leaf)
    return refs


class TraceSweeper:
    """Double-buffer a queue of (model, trace) jobs through the shared
    cached executable."""

    def __init__(
        self,
        cfg: TaoConfig,
        ecfg: EngineConfig = EngineConfig(),
        *,
        depth: int = 2,
        async_prepare: Optional[bool] = None,
        store=None,
    ):
        if depth < 1:
            raise ValueError(f"queue depth must be >= 1, got {depth}")
        # Sharded sweeps are a composition: the engines the consumer builds
        # all resolve the same ExecutionPlan from this config, so the trace
        # queue fans out over models/traces while each step fans out over
        # the plan's batch axes.  Resolve eagerly so a bad (mesh, batch)
        # combination fails here, not mid-sweep.
        self.plan = ExecutionPlan.resolve(
            ecfg.mesh, batch_size=ecfg.batch_size, plan=ecfg.plan
        )
        self.cfg = cfg
        self.ecfg = ecfg
        self.depth = depth
        # Thread the host-side preparation only when an accelerator runs the
        # step: on a CPU-only backend the "device" compute occupies the same
        # cores, so a producer thread is pure contention (measured ~0.7x at
        # tiny scale) — prepare inline there instead (the per-trace feature
        # dedup still applies).  Overridable for tests / exotic hosts.
        if async_prepare is None:
            async_prepare = jax.default_backend() != "cpu"
        self.async_prepare = async_prepare
        # content-addressed artifact store (repro.store.ArtifactStore):
        # inference features persist/load across processes through it
        self.store = store
        # the last stacked model set: (treedef, leaf refs, engine); one kept,
        # dropped before the next is built, so at most one stack is resident
        self._stack: Optional[tuple] = None

    def warmup(
        self, trace_lengths: Iterable[int], *, heads: int = 1
    ) -> Dict[str, int]:
        """AOT-compile the sweep's step for a declared geometry set before
        any jobs (or even params) exist: abstract params from
        ``jax.eval_shape`` lower through ``StreamingEngine.warmup``, and —
        with the persistent compilation cache enabled — a process that
        warms the same geometries later deserializes instead of compiling.
        ``heads`` is the number of models the sweep will run over each
        trace (its stacked step; 1 warms the one-model step).
        Returns ``{"geometries": ..., "aot_compiled": ...}``."""
        engine = StreamingEngine(
            abstract_params(self.cfg, heads), self.cfg, self.ecfg, heads=heads
        )
        entries = [engine.warmup(n) for n in sorted(set(trace_lengths))]
        return {
            "geometries": len(entries),
            "aot_compiled": sum(1 for e in entries if e.aot is not None),
        }

    # host-side preparation that the producer thread runs ahead of the device
    # producer-thread / inline feature prep: host NumPy on the raw trace,
    # runs before the trace's first dispatch
    # tao: cold
    def _prepare(
        self,
        job: SweepJob,
        cache: Dict[str, FeatureSet],
        digests: Dict[int, str],
        counts: Dict[str, int],
    ) -> Optional[FeatureSet]:
        fault_point("scheduler.prepare", payload=job.key)
        if self.ecfg.feature_backend == "fused":
            # device-side extraction happens in the consumer (the device is
            # the contended resource); nothing to pre-compute on host.
            return None
        # DSE sweeps visit the same few traces once per design point: the
        # features are a pure function of (trace, FeatureConfig), so extract
        # each distinct trace once and share it across every model.  Dedup
        # is by *content* digest — the same identity scheme the artifact
        # store keys on — so two equal trace arrays loaded separately
        # still share one extraction (object ids would not).
        dg = digests.get(id(job.trace))
        if dg is None:
            dg = array_digest(job.trace)
            digests[id(job.trace)] = dg
        fs = cache.get(dg)
        if fs is not None:
            return fs
        key = content_key("features", dg, self.cfg.features)
        if self.store is not None:
            hit = self.store.get("features", key)
            if hit is not None:
                from ..store.store import tree_to_features

                fs = tree_to_features(hit[0])
                counts["from_store"] += 1
                cache[dg] = fs
                return fs
        fs = extract_features(job.trace, self.cfg.features, with_labels=False)
        counts["extracted"] += 1
        if self.store is not None:
            from ..store.store import features_to_tree

            self.store.put("features", key, features_to_tree(fs))
        cache[dg] = fs
        return fs

    def _progress_token(self) -> str:
        """Everything a sweep result is a function of besides (params,
        trace): model config, batch geometry, collect flag, spec set —
        part of every progress-manifest key so a resumed run with a
        different recipe never reuses stale results."""
        specs = resolve_metrics(self.ecfg.metrics)
        return "|".join((
            str(config_token(self.cfg)),
            f"b{self.ecfg.batch_size}",
            f"c{int(self.ecfg.collect)}",
            ",".join(s.name for s in specs),
        ))

    # tao: hot
    def run(
        self, jobs: Iterable[SweepJob], *, resume_key: Optional[str] = None
    ) -> SweepReport:
        jobs = list(jobs)
        if not jobs:
            raise ValueError("sweep needs at least one job")
        keys = [j.key for j in jobs]
        if len(set(keys)) != len(keys):
            raise ValueError(f"duplicate sweep job keys: {keys}")
        if resume_key is not None and self.store is None:
            raise ValueError("resume_key needs a store to hold the manifests")

        feat_cache: Dict[str, FeatureSet] = {}  # trace digest -> features
        digests: Dict[int, str] = {}            # id(trace) -> digest (memo)
        feat_counts = {"extracted": 0, "from_store": 0}
        occ: List[int] = []
        results: Dict[str, SimulationResult] = {}
        n_instr = 0
        n_total = len(jobs)

        # crash-resume: load the done set up front and only feed the
        # remainder to the producer — completed jobs cost zero extractions
        # and zero device work on the resumed run
        skipped = 0
        progress_keys: Dict[str, str] = {}
        if resume_key is not None:
            from ..resilience import manifest as _manifest

            token = self._progress_token()
            pdigests: Dict[int, str] = {}       # id(params) -> digest (memo)
            remaining: List[SweepJob] = []
            for job in jobs:
                dg = digests.get(id(job.trace))
                if dg is None:
                    dg = array_digest(job.trace)
                    digests[id(job.trace)] = dg
                pd = pdigests.get(id(job.params))
                if pd is None:
                    pd = tree_digest(job.params)
                    pdigests[id(job.params)] = pd
                pkey = _manifest.sweep_progress_key(
                    resume_key, job.key, dg, pd, token
                )
                progress_keys[job.key] = pkey
                res = _manifest.load_sweep_result(self.store, pkey)
                if res is not None:
                    results[job.key] = res
                    n_instr += res.num_instructions
                    skipped += 1
                else:
                    remaining.append(job)
            jobs = remaining

        # trace-major: one group per trace array, in first-appearance order;
        # each group is one simulate over every model it holds
        by_trace: Dict[int, List[SweepJob]] = {}
        for job in jobs:
            by_trace.setdefault(id(job.trace), []).append(job)
        groups = list(by_trace.values())

        # consumer state: engines share jitted steps via the process-wide
        # step cache; one-model engines per params object (reused across
        # that model's traces), stacked ones kept by the sweeper
        engines: Dict[int, StreamingEngine] = {}
        entries: Dict[int, object] = {}   # id(_CachedStep) -> _CachedStep
        baseline: Dict[int, int] = {}     # compiles before this sweep used it
        counts = {"stacks": 0, "extractions": 0, "steps": 0, "head_steps": 0}

        def engine_for(group: List[SweepJob]) -> StreamingEngine:
            if len(group) > 1:
                return self._stacked_engine([j.params for j in group], counts)
            params = group[0].params
            engine = engines.get(id(params))
            if engine is None:
                engine = StreamingEngine(params, self.cfg, self.ecfg)
                engines[id(params)] = engine
            return engine

        def consume(
            index: int, group: List[SweepJob], features: Optional[FeatureSet]
        ) -> None:
            nonlocal n_instr
            for job in group:
                fault_point("scheduler.consume", payload=job.key)
            trace = group[0].trace
            engine = engine_for(group)
            # snapshot the shared step entry BEFORE simulating, so the
            # report attributes only compiles this sweep triggered
            entry = engine.step_entry_for(len(trace))
            if id(entry) not in entries:
                entries[id(entry)] = entry
                baseline[id(entry)] = entry.compiles
            nw = num_windows(len(trace), self.cfg.window, self.cfg.window)
            batches = -(-nw // self.ecfg.batch_size)
            before = engine.extractions
            with span("sweep.group", trace=index, heads=len(group), batches=batches):
                out = engine.simulate_heads(trace, features=features)
            counts["extractions"] += engine.extractions - before
            counts["steps"] += batches
            counts["head_steps"] += batches * len(group)
            for job, res in zip(group, out):
                results[job.key] = res
                n_instr += res.num_instructions
                if resume_key is not None:
                    from ..resilience import manifest as _manifest

                    _manifest.publish_sweep_result(
                        self.store, progress_keys[job.key], res
                    )

        t0 = time.perf_counter()
        with call_span(
            "sweep.call",
            jobs=len(jobs),
            traces=len(groups),
            heads=len({id(j.params) for j in jobs}),
        ):
            if not self.async_prepare:
                # inline mode (CPU backends): no producer thread to contend
                # with the step's compute; the feature dedup still applies
                for i, group in enumerate(groups):
                    consume(
                        i, group,
                        self._prepare(group[0], feat_cache, digests, feat_counts),
                    )
            else:
                self._run_async(groups, consume, feat_cache, digests, feat_counts, occ)
        secs = time.perf_counter() - t0

        return SweepReport(
            results={k: results[k] for k in keys},
            seconds=secs,
            num_traces=n_total,
            num_instructions=n_instr,
            num_compiles=sum(
                e.compiles - baseline[i] for i, e in entries.items()
            ),
            traces_per_s=n_total / secs,
            mips=n_instr / 1e6 / secs,
            queue_occupancy_mean=float(np.mean(occ)) if occ else 0.0,  # tao: noqa[TAO002] occ is a host list of queue depths; runs once after the sweep loop
            queue_occupancy_max=int(np.max(occ)) if occ else 0,
            queue_depth=self.depth,
            prepared_async=self.async_prepare,
            plan_kind=self.plan.kind,
            num_shards=self.plan.num_shards,
            features_extracted=feat_counts["extracted"],
            features_from_store=feat_counts["from_store"],
            jobs_skipped=skipped,
            heads_per_step=(
                counts["head_steps"] / counts["steps"] if counts["steps"] else 0.0
            ),
            extractions=counts["extractions"],
            stacks_built=counts["stacks"],
        )

    def _run_async(self, groups, consume, feat_cache, digests, feat_counts, occ):
        """The producer thread prepares each group ahead of the consumer
        (this thread) through a bounded queue."""
        q: "queue.Queue" = queue.Queue(maxsize=self.depth)
        error: List[BaseException] = []
        stop = threading.Event()  # set when the consumer bails out early

        def produce():
            try:
                for i, group in enumerate(groups):
                    prepared = self._prepare(
                        group[0], feat_cache, digests, feat_counts
                    )
                    while not stop.is_set():
                        try:
                            q.put((i, group, prepared), timeout=0.1)
                            break
                        except queue.Full:
                            continue
                    if stop.is_set():
                        return
            except BaseException as e:  # surfaced in the consumer
                error.append(e)
            finally:
                while True:  # always deliver _STOP without blocking
                    try:
                        q.put(_STOP, timeout=0.1)
                        break
                    except queue.Full:
                        if stop.is_set():
                            break

        producer = threading.Thread(
            target=produce, name="trace-sweep-producer", daemon=True
        )
        producer.start()
        try:
            while True:
                occ.append(q.qsize())
                item = q.get()
                if item is _STOP:
                    break
                consume(*item)
        finally:
            # unblock the producer (it may be parked on a full queue)
            # and drop any prepared-but-unconsumed feature arrays
            stop.set()
            while True:
                try:
                    q.get_nowait()
                except queue.Empty:
                    break
        producer.join()
        if error:
            raise error[0]

    def _stacked_engine(self, params: List[Dict], counts: Dict[str, int]) -> StreamingEngine:
        """The engine over ``params`` stacked on a leading axis: the kept
        one while every leaf is still the same object, else built anew
        (the kept stack dropped first) and placed as the plan places
        params."""
        treedef = jax.tree_util.tree_structure(params)
        leaves = jax.tree_util.tree_leaves(params)
        kept = self._stack
        if (
            kept is not None
            and kept[0] == treedef
            and all(r() is x for r, x in zip(kept[1], leaves))
        ):
            return kept[2]
        self._stack = None
        engine = StreamingEngine(
            self.plan.replicate(stack_params(params)), self.cfg, self.ecfg,
            heads=len(params),
        )
        self._stack = (treedef, _leaf_refs(params), engine)
        counts["stacks"] += 1
        return engine


def sweep_traces(
    cfg: TaoConfig,
    jobs: Iterable[Tuple[str, Dict, np.ndarray]],
    ecfg: EngineConfig = EngineConfig(),
    *,
    depth: int = 2,
    async_prepare: Optional[bool] = None,
) -> SweepReport:
    """One-shot convenience wrapper over ``TraceSweeper``."""
    return TraceSweeper(cfg, ecfg, depth=depth, async_prepare=async_prepare).run(
        SweepJob(k, p, t) for k, p, t in jobs
    )
