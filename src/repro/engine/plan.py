"""ExecutionPlan: one partitioning decision, consumed everywhere.

The engine's shard_map path, the sweep scheduler, and the streaming
trainer all need the same four answers when a mesh is (or is not) in
play:

  1. *placement* — how a host batch lands on device(s)
     (``device_put``: plain transfer, batch-sharded NamedSharding, or —
     multi-host — assembly from per-process shards);
  2. *wrapping* — whether a step body runs plain or under
     ``jax.shard_map`` (``wrap``);
  3. *index mapping* — how a shard-local row index becomes a global
     batch/window index (``AxisContext.shard_index``);
  4. *reduction* — how metric/grad partial sums cross shards
     (``AxisContext.psum``/``pmax``, identity off-mesh).

Before this module those answers were re-derived ad hoc at every
``ecfg.mesh is not None`` branch in the runner (and forbidden outright in
the scheduler).  Now they resolve **once** into an ``ExecutionPlan`` —
a frozen, hashable value that participates in the step-cache key, so a
single-device plan and an 8-way plan are just two cache entries of the
same machinery, sharded sweeps are a composition (trace queue × ``data``
axis) rather than a third copy of the branching, and the one-compile-
per-geometry guarantee extends to every path.

Plans are *pure partitioning*: mesh construction and multi-host bring-up
live in ``repro.distributed`` (``data_mesh`` / ``initialize_multihost`` /
``virtual_cpu_devices``).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from ..compat import Mesh, NamedSharding, PartitionSpec as P, shard_map
from ..distributed.sharding import logical_to_spec

__all__ = ["AxisContext", "ExecutionPlan"]


@dataclasses.dataclass(frozen=True)
class AxisContext:
    """The traced-side face of a plan: cross-shard reducers plus the
    shard-index expression.  Every method is safe to call inside a jitted
    (and shard_mapped) step body; off-mesh they degrade to identities.
    """

    axes: Tuple[str, ...]        # mesh axes carrying the batch dimension
    sizes: Tuple[int, ...]       # their extents (row-major index order)

    @property
    def num_shards(self) -> int:
        n = 1
        for s in self.sizes:
            n *= s
        return n

    def psum(self, x):
        """Cross-shard sum (identity when the plan is single-device)."""
        return jax.lax.psum(x, self.axes) if self.axes else x

    def pmax(self, x):
        """Cross-shard max (identity when the plan is single-device)."""
        return jax.lax.pmax(x, self.axes) if self.axes else x

    def shard_index(self):
        """This shard's row-major linear index over the batch axes, as a
        traced int32 scalar (0 when single-device)."""
        if not self.axes:
            return jnp.int32(0)
        idx = jnp.int32(0)
        for a, s in zip(self.axes, self.sizes):
            idx = idx * s + jax.lax.axis_index(a)
        return idx


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """How one step executes over (possibly) many devices.

    Resolve once per ``EngineConfig`` (or trainer invocation) via
    :meth:`resolve`; the object is hashable and equality-comparable, so
    it slots directly into step-cache keys — two engines resolving the
    same mesh share one compiled executable.
    """

    kind: str                             # "single" | "sharded"
    mesh: Optional[Mesh] = None
    batch_axes: Tuple[str, ...] = ()      # mesh axes carrying "batch"

    # ---- construction ---------------------------------------------------

    @classmethod
    def single(cls) -> "ExecutionPlan":
        """The trivial plan: one device, identity reducers."""
        return cls(kind="single")

    @classmethod
    def resolve(
        cls,
        mesh: Optional[Mesh] = None,
        *,
        batch_size: int,
        plan: Optional["ExecutionPlan"] = None,
    ) -> "ExecutionPlan":
        """The plan for an (optional) mesh and a batch size.

        ``plan`` passes through after validation (its mesh wins; passing
        a *different* mesh alongside it is an error).  Without a mesh the
        result is the single-device plan.  With one, the rules table in
        ``distributed/sharding.py`` decides which mesh axes carry the
        ``batch`` logical axis (divisibility-checked against
        ``batch_size``); a mesh with no usable batch axes is rejected.
        """
        if plan is not None:
            if mesh is not None and mesh is not plan.mesh and mesh != plan.mesh:
                raise ValueError(
                    "both plan= and a different mesh= were given; the plan "
                    "already owns its mesh — pass one or the other"
                )
            plan.validate_batch(batch_size)
            return plan
        if mesh is None:
            return cls.single()
        spec = logical_to_spec(("batch",), shape=(batch_size,), mesh=mesh)
        entry = spec[0] if len(spec) else None
        if entry is None:
            raise ValueError(
                f"cannot shard batch_size={batch_size} over mesh "
                f"{dict(mesh.shape)}: no usable 'batch' mesh axes "
                "(see distributed.sharding.LOGICAL_RULES)"
            )
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        return cls(kind="sharded", mesh=mesh, batch_axes=axes)

    @classmethod
    def auto(cls, batch_size: int) -> "ExecutionPlan":
        """Sharded over all visible devices when there are several
        (``distributed.data_mesh()``), single-device otherwise."""
        if len(jax.devices()) > 1:
            from ..distributed.multihost import data_mesh

            return cls.resolve(data_mesh(), batch_size=batch_size)
        return cls.single()

    def __post_init__(self):
        if self.kind not in ("single", "sharded"):
            raise ValueError(f"plan kind must be single|sharded, got {self.kind!r}")
        if self.kind == "sharded" and (self.mesh is None or not self.batch_axes):
            raise ValueError("a sharded plan needs a mesh and batch axes")
        if self.kind == "single" and self.mesh is not None:
            raise ValueError("a single-device plan must not carry a mesh")

    # ---- queries --------------------------------------------------------

    @property
    def sharded(self) -> bool:
        return self.kind == "sharded"

    @property
    def num_shards(self) -> int:
        n = 1
        for a in self.batch_axes:
            n *= self.mesh.shape[a]
        return n

    def divides(self, batch_size: int) -> bool:
        """Whether ``batch_size`` rows split evenly over the plan's shards."""
        return batch_size % self.num_shards == 0

    def validate_batch(self, batch_size: int) -> None:
        """Reject batch sizes the plan cannot split evenly (shard_map jit
        arguments do not support uneven padding)."""
        if not self.divides(batch_size):
            raise ValueError(
                f"batch_size={batch_size} does not divide over the plan's "
                f"{self.num_shards} shards (axes {self.batch_axes} of mesh "
                f"{dict(self.mesh.shape) if self.mesh else {}})"
            )

    def local_batch(self, batch_size: int) -> int:
        """Rows of a global batch each shard sees."""
        return batch_size // self.num_shards

    # ---- the four answers ----------------------------------------------

    def batch_spec(self) -> P:
        """PartitionSpec splitting a leading batch dimension."""
        if not self.sharded:
            return P()
        return P(self.batch_axes if len(self.batch_axes) > 1 else self.batch_axes[0])

    def batch_sharding(self) -> Optional[NamedSharding]:
        if not self.sharded:
            return None
        return NamedSharding(self.mesh, self.batch_spec())

    def device_put(self, batch):
        """Place a host batch (any pytree of leading-batch-dim arrays)
        according to the plan: plain transfer single-device, batch-
        sharded NamedSharding on a mesh, per-process assembly under
        multi-host.  Every process streams the same trace, so each holds
        the full global batch and contributes only its contiguous row
        slice (``data_mesh`` orders devices by process, so process ``p``
        owns rows ``[p*B/P, (p+1)*B/P)``)."""
        if not self.sharded:
            return jax.device_put(batch)
        sh = self.batch_sharding()
        pc = jax.process_count()
        if pc > 1:
            pi = jax.process_index()

            def put(v):
                n = v.shape[0]
                if n % pc:
                    raise ValueError(
                        f"global batch of {n} rows does not split over "
                        f"{pc} processes"
                    )
                per = n // pc
                return jax.make_array_from_process_local_data(
                    sh, v[pi * per : (pi + 1) * per]
                )

            return jax.tree.map(put, batch)
        return jax.device_put(batch, sh)

    def replicate(self, tree):
        """Place a pytree fully replicated across the plan's mesh (model
        params / optimizer state for data-parallel training).  Identity
        placement on the single-device plan — jit commits as usual."""
        if not self.sharded:
            return tree
        return jax.device_put(tree, NamedSharding(self.mesh, P()))

    def wrap(self, fn, in_specs, out_specs):
        """``shard_map`` the body on a sharded plan; identity otherwise.
        Callers jit the result either way."""
        if not self.sharded:
            return fn
        return shard_map(fn, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs)

    def axis_context(self) -> AxisContext:
        """The traced-side reducers/index mapping (see ``AxisContext``)."""
        if not self.sharded:
            return AxisContext(axes=(), sizes=())
        return AxisContext(
            axes=self.batch_axes,
            sizes=tuple(self.mesh.shape[a] for a in self.batch_axes),
        )

    def describe(self) -> dict:
        """JSON-friendly summary (bench artifacts, reports)."""
        return {
            "kind": self.kind,
            "num_shards": self.num_shards,
            "batch_axes": list(self.batch_axes),
            "mesh_shape": dict(self.mesh.shape) if self.mesh is not None else {},
        }

    def cache_token(self) -> tuple:
        """Serializable identity for content-addressed cache keys
        (``repro.store``): the partitioning *shape* — kind, batch axes,
        mesh axis extents — with device objects excluded, so the same
        logical plan resolved in two processes (whose ``Mesh`` objects
        can never compare equal) maps to the same key."""
        return (
            "plan",
            self.kind,
            tuple(self.batch_axes),
            tuple(sorted(dict(self.mesh.shape).items()))
            if self.mesh is not None
            else (),
        )
