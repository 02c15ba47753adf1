"""Logical-axis sharding rules (MaxText-style) for the multi-pod runtime.

Model code annotates activations/params with *logical* axis names; a rule
table maps them to mesh axes.  The mapper checks divisibility and silently
falls back to replication per-dimension, so every (arch × shape × mesh)
combination lowers even when e.g. 40 KV heads don't divide a 16-way model
axis.

Meshes:
  single-pod  (data=16, model=16)
  multi-pod   (pod=2, data=16, model=16)   — "pod" only ever carries batch.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax

from ..compat import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "LOGICAL_RULES",
    "mesh_context",
    "current_mesh",
    "logical_to_spec",
    "shard",
    "named_sharding",
    "spec_for_shape",
    "tree_shardings",
]

AxisSpec = Union[str, Tuple[str, ...], None]

# logical axis -> preferred mesh axes (joined), in priority order.
# "batch" spans the pod axis too: pure data parallelism across pods.
LOGICAL_RULES: Dict[str, Tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "seq": ("model",),          # sequence sharding (Megatron-SP style)
    "embed": (),                # residual d_model stays unsharded in activations
    "heads": ("model",),        # TP over attention heads
    "kv_heads": ("model",),
    # fallback TP dim: when a head count doesn't divide the model axis the
    # head_dim (always a multiple of 16 in the zoo) picks up the sharding
    "head_dim": ("model",),
    "mlp": ("model",),          # TP over FFN hidden
    "experts": ("model",),      # EP
    "expert_mlp": (),
    "vocab": ("model",),        # TP over vocab (embed + logits)
    "fsdp": ("data",),          # param d_model dim -> FSDP shard
    "conv": (),
    "state": (),
    "lru": ("model",),
    "cache_seq": ("model",),    # decode KV cache sharded along sequence
    "cache_batch": ("pod", "data"),
    "frames": (),
    "stack": (),                # scan-stacked layer dim, never sharded
}

_local = threading.local()


@contextlib.contextmanager
def mesh_context(mesh: Mesh, rules: Optional[Dict[str, Tuple[str, ...]]] = None):
    """Install a mesh + rule table; `shard()` is a no-op outside of it."""
    prev = getattr(_local, "ctx", None)
    _local.ctx = (mesh, rules or LOGICAL_RULES)
    try:
        with jax.set_mesh(mesh):
            yield mesh
    finally:
        _local.ctx = prev


def current_mesh() -> Optional[Mesh]:
    ctx = getattr(_local, "ctx", None)
    return ctx[0] if ctx else None


def _mesh_axis_size(mesh: Mesh, axes: Tuple[str, ...]) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def logical_to_spec(
    logical_axes: Sequence[Optional[str]],
    shape: Optional[Sequence[int]] = None,
    mesh: Optional[Mesh] = None,
    rules: Optional[Dict[str, Tuple[str, ...]]] = None,
    allow_uneven: bool = False,
) -> P:
    """Map logical axis names to a PartitionSpec, checking divisibility when
    `shape` is given and degrading gracefully:

      * drop mesh axes missing from the mesh (e.g. "pod" on single-pod)
      * if the full axis-product doesn't divide the dim, try prefixes
      * replicate as the final fallback
    """
    ctx = getattr(_local, "ctx", None)
    if mesh is None and ctx:
        mesh = ctx[0]
    if rules is None:
        rules = (ctx[1] if ctx else LOGICAL_RULES)
    parts = []
    used: set = set()
    for i, name in enumerate(logical_axes):
        entry: AxisSpec = None
        if name is not None and mesh is not None:
            cand = tuple(a for a in rules.get(name, ()) if a in mesh.shape and a not in used)
            # prefer the longest prefix that divides the dim evenly
            want = cand
            while want:
                if shape is None or shape[i] % _mesh_axis_size(mesh, want) == 0:
                    break
                want = want[:-1]
            if not want and cand and shape is not None and allow_uneven:
                # GSPMD supports uneven (padded) sharding for activation
                # constraints (NOT for jit argument shardings); accept it when
                # the padding waste is < 2x (dim*2 >= shards): 40 heads on a
                # 16-way model axis pads to 48 instead of replicating 16x.
                uneven = cand
                while uneven:
                    if 2 * shape[i] >= _mesh_axis_size(mesh, uneven):
                        want = uneven
                        break
                    uneven = uneven[:-1]
            if want:
                entry = want if len(want) > 1 else want[0]
                used.update(want)
        parts.append(entry)
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def shard(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """Apply a logical sharding constraint; no-op without a mesh context."""
    ctx = getattr(_local, "ctx", None)
    if ctx is None:
        return x
    mesh, rules = ctx
    spec = logical_to_spec(
        logical_axes, shape=x.shape, mesh=mesh, rules=rules, allow_uneven=True
    )
    return jax.lax.with_sharding_constraint(x, spec)


def named_sharding(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def spec_for_shape(
    mesh: Mesh,
    logical_axes: Sequence[Optional[str]],
    shape: Sequence[int],
    rules: Optional[Dict[str, Tuple[str, ...]]] = None,
    allow_uneven: bool = False,
) -> NamedSharding:
    """NamedSharding for one array; forwards ``allow_uneven`` so callers
    get the same padded-sharding acceptance window as ``shard()``."""
    return NamedSharding(
        mesh,
        logical_to_spec(
            logical_axes, shape, mesh, rules, allow_uneven=allow_uneven
        ),
    )


def _is_axes_leaf(x) -> bool:
    """Logical-axis leaves are plain tuples of str/None (not NamedTuples,
    which are pytree nodes — e.g. TrainState axis trees)."""
    if x is None:
        return True
    return (
        isinstance(x, tuple)
        and not hasattr(x, "_fields")
        and all(e is None or isinstance(e, str) for e in x)
    )


def tree_shardings(tree_axes, tree_shapes, mesh: Mesh, rules=None):
    """Map a pytree of logical-axis tuples + matching shape pytree to
    NamedShardings (replicated where axes are None).

    The one partitioning helper the trainer, the launch dry-run, and the
    serving path share — hoisted here so every layer resolves logical
    axes through the same rule table.  ``tree_shapes`` leaves need only a
    ``.shape`` (ShapeDtypeStructs or arrays).
    """

    def one(axes, sds):
        if axes is None:
            return NamedSharding(mesh, P())
        return spec_for_shape(mesh, axes, sds.shape, rules)

    return jax.tree.map(one, tree_axes, tree_shapes, is_leaf=_is_axes_leaf)
