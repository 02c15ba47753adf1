"""Windowed training dataset construction over extracted features.

The model consumes windows of W = N+1 consecutive instructions and predicts
metrics for every position (causal attention), which is the batched
equivalent of the paper's "current instruction + N context instructions"
formulation.  Duplicate windows are removed (the paper de-duplicates
samples during preprocessing).

Windowing is zero-copy: `window_view` returns a strided view
(`np.lib.stride_tricks.sliding_window_view`) so a trace of N instructions
costs O(N) memory regardless of the window/stride combination; data is only
materialized per-batch by `WindowDataset.batches` / the streaming engine.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Dict, Iterator, List, Optional, Sequence

import numpy as np

from ..spans import span
from .features import FeatureSet

__all__ = [
    "WindowDataset",
    "StreamingWindowDataset",
    "build_windows",
    "window_view",
    "num_windows",
    "stream_batches",
    "iter_window_digests",
    "concat_datasets",
    "INPUT_KEYS",
]


def num_windows(n: int, window: int, stride: int) -> int:
    """Number of windows the grid `range(0, max(1, n - window + 1), stride)`
    produces — the single source of truth shared by every windowing path."""
    return len(range(0, max(1, n - window + 1), stride))


def window_view(arr: np.ndarray, window: int, stride: int) -> np.ndarray:
    """(N, ...) -> zero-copy (num_windows, window, ...) strided view.

    Matches the legacy copying grid exactly, including the n < window case
    (a single truncated window, which genuinely requires a 1-row copy).
    """
    n = len(arr)
    if n < window:
        return arr[np.newaxis]
    view = np.lib.stride_tricks.sliding_window_view(arr, window, axis=0)
    # sliding_window_view appends the window axis last; put it after the
    # window-count axis (still a view — only strides change).
    view = np.moveaxis(view, -1, 1)
    return view[::stride]

INPUT_KEYS = ("opcode", "regbits", "flags", "brhist", "memdist")
_INPUT_KEYS = INPUT_KEYS  # internal alias
_LABEL_KEYS = (
    "fetch_lat",
    "exec_lat",
    "mispred",
    "dlevel",
    "icache_miss",
    "tlb_miss",
    "is_branch",
    "is_mem",
)


@dataclasses.dataclass
class WindowDataset:
    """Stacked windows: inputs[k] has shape (num_windows, W, ...)."""

    inputs: Dict[str, np.ndarray]
    labels: Optional[Dict[str, np.ndarray]]

    def __len__(self) -> int:
        return len(self.inputs["opcode"])

    @property
    def window(self) -> int:
        return self.inputs["opcode"].shape[1]

    def batches(
        self, batch_size: int, rng: Optional[np.random.Generator] = None, drop_last: bool = True
    ) -> Iterator[Dict[str, np.ndarray]]:
        n = len(self)
        order = np.arange(n)
        if rng is not None:
            rng.shuffle(order)
        stop = n - (n % batch_size) if drop_last else n
        for lo in range(0, stop, batch_size):
            with span("feed.gather"):
                idx = order[lo : lo + batch_size]
                out = {k: v[idx] for k, v in self.inputs.items()}
                if self.labels is not None:
                    out["labels"] = {k: v[idx] for k, v in self.labels.items()}
            yield out

    def subsample(self, n: int, seed: int = 0) -> "WindowDataset":
        if n >= len(self):
            return self
        idx = np.random.default_rng(seed).choice(len(self), size=n, replace=False)
        return WindowDataset(
            inputs={k: v[idx] for k, v in self.inputs.items()},
            labels=None
            if self.labels is None
            else {k: v[idx] for k, v in self.labels.items()},
        )


def build_windows(
    fs: FeatureSet,
    window: int,
    stride: Optional[int] = None,
    dedup: bool = True,
) -> WindowDataset:
    stride = stride or window

    def _stack(arr: np.ndarray) -> np.ndarray:
        return window_view(arr, window, stride)

    inputs = {
        "opcode": _stack(fs.opcode),
        "regbits": _stack(fs.regbits),
        "flags": _stack(fs.flags),
        "brhist": _stack(fs.brhist),
        "memdist": _stack(fs.memdist),
    }
    labels = None
    if fs.labels is not None:
        labels = {k: _stack(fs.labels[k]) for k in _LABEL_KEYS}

    if dedup:
        keep = _dedup_mask(inputs, labels)
        inputs = {k: v[keep] for k, v in inputs.items()}
        if labels is not None:
            labels = {k: v[keep] for k, v in labels.items()}

    return WindowDataset(inputs=inputs, labels=labels)


def _pad_rows(arr: np.ndarray, rows: int) -> np.ndarray:
    out = np.zeros((rows,) + arr.shape[1:], dtype=arr.dtype)
    out[: len(arr)] = arr
    return out


def stream_batches(
    fs: FeatureSet,
    window: int,
    batch_size: int,
    stride: Optional[int] = None,
    pad: bool = True,
    extra: Optional[Dict[str, np.ndarray]] = None,
    rows: Optional[Sequence[int]] = None,
) -> Iterator[Dict[str, np.ndarray]]:
    """Stream fixed-shape window batches without materializing all windows.

    Windows come from zero-copy `window_view`s; each yielded batch is the only
    materialized copy, so peak host memory is O(trace + batch) even for
    multi-million-instruction traces.  Every batch carries a float32 "valid"
    mask of shape (batch_size, W); when `pad` is set the final ragged batch is
    zero-padded to `batch_size` rows (mask rows 0) so a single jit
    compilation covers the whole stream.  `rows`, when given, is the row
    count of each batch in turn (the engine's batch plan,
    ``StreamingEngine.batch_rows``): batch i takes the next `rows[i]`
    windows and is padded to `rows[i]` rows.  `extra` arrays (e.g. the
    trace's is_branch/is_mem columns) are windowed on the same grid and
    yielded alongside the feature keys.
    """
    stride = stride or window
    views = {k: window_view(getattr(fs, k), window, stride) for k in _INPUT_KEYS}
    if extra:
        views.update({k: window_view(v, window, stride) for k, v in extra.items()})
    nw = len(views["opcode"])
    w_eff = views["opcode"].shape[1]
    if rows is None:
        rows = [batch_size] * -(-nw // batch_size)
    elif sum(rows) < nw:
        raise ValueError(f"batch rows {tuple(rows)} hold fewer than the {nw} windows")
    lo = 0
    for size in rows:
        hi = min(lo + size, nw)
        n_rows = size if pad else hi - lo
        batch = {k: _pad_rows(v[lo:hi], n_rows) for k, v in views.items()}
        valid = np.zeros((n_rows, w_eff), dtype=np.float32)
        valid[: hi - lo] = 1.0
        batch["valid"] = valid
        yield batch
        lo = hi


# windows hashed per contiguous block by iter_window_digests
_DEDUP_CHUNK = 2048


def iter_window_digests(
    inputs: Dict, labels: Optional[Dict], chunk: int = _DEDUP_CHUNK
) -> Iterator[bytes]:
    """Per-window blake2b digest stream, hashing contiguous row-blocks.

    Byte-compatible with the original per-row loop — a blake2b stream over
    concatenated updates equals one update over the concatenation, so
    assembling each window's bytes (opcode, memdist, brhist, then
    fetch/exec latencies when labels are present) into ONE contiguous row
    yields the exact same digests.  Per ``chunk`` windows the source arrays
    are block-copied into a single (rows, row_bytes) uint8 matrix and each
    row is hashed with one one-shot blake2b call over a zero-copy
    memoryview slice, replacing 3-5 per-row NumPy indexing + ``tobytes``
    copies + hash updates per window.  The remaining cost is the blake2b
    compression itself.  Works directly on zero-copy strided window views —
    at most ``chunk`` windows are materialized at a time, never the whole
    window set.
    """
    arrays = [inputs["opcode"], inputs["memdist"], inputs["brhist"]]
    if labels is not None:
        arrays += [labels["fetch_lat"], labels["exec_lat"]]
    n = len(arrays[0])
    row_bytes = [
        a.dtype.itemsize * int(np.prod(a.shape[1:], dtype=np.int64))
        for a in arrays
    ]
    total = sum(row_bytes)
    blake2b = hashlib.blake2b
    for lo in range(0, n, chunk):
        hi = min(lo + chunk, n)
        rows = hi - lo
        buf = np.empty((rows, total), np.uint8)
        col = 0
        for a, rb in zip(arrays, row_bytes):
            blk = np.ascontiguousarray(a[lo:hi])
            buf[:, col : col + rb] = blk.view(np.uint8).reshape(rows, rb)
            col += rb
        mv = memoryview(buf).cast("B")
        for i in range(rows):
            yield blake2b(
                mv[i * total : (i + 1) * total], digest_size=16
            ).digest()


def _dedup_mask(
    inputs: Dict, labels: Optional[Dict], seen: Optional[set] = None
) -> np.ndarray:
    """Drop windows whose (features, labels) content is byte-identical.

    ``seen`` — a digest reservoir (16 B per unique window) — lets streaming
    callers carry the keep-set across traces; by default each call dedups
    independently, exactly like the original per-row implementation.
    """
    n = len(inputs["opcode"])
    if seen is None:
        seen = set()
    keep = np.zeros(n, dtype=bool)
    for i, d in enumerate(iter_window_digests(inputs, labels)):
        if d not in seen:
            seen.add(d)
            keep[i] = True
    return keep


@dataclasses.dataclass
class _StreamPart:
    """One trace's zero-copy window views (plus label views)."""

    inputs: Dict[str, np.ndarray]
    labels: Optional[Dict[str, np.ndarray]]


class StreamingWindowDataset:
    """O(trace + batch) drop-in for ``WindowDataset`` over 1..N feature sets.

    Construction keeps only zero-copy ``window_view``s of the underlying
    ``FeatureSet`` arrays plus the streaming-dedup keep set (a blake2b
    digest reservoir: O(unique windows) memory, bit-identical keep set to
    ``_dedup_mask``).  ``batches`` shuffles a *window-index* permutation and
    gathers every batch straight out of the strided views, so peak host
    memory is O(traces + one batch) instead of O(all windows) — nothing
    beyond the yielded batch is ever materialized.

    ``dedup_scope="trace"`` (default) dedups each feature set independently,
    mirroring the materialized pipeline (``concat_datasets`` of per-trace
    ``build_windows``) — this is what makes the keep set, batch stream, and
    therefore the whole training trajectory bit-identical to the
    materialized path under the same seed.  ``"global"`` shares one
    reservoir across traces for strictly stronger dedup on multi-trace
    corpora.

    Interchangeable with ``WindowDataset`` wherever the ``batches`` /
    ``subsample`` / ``len`` contract is used (the trainers, the Session
    facade); the stacked ``.inputs``/``.labels`` arrays intentionally do
    not exist here — call ``materialize()`` when a consumer genuinely
    needs every window in memory.
    """

    def __init__(
        self,
        features,
        window: int,
        stride: Optional[int] = None,
        dedup: bool = True,
        dedup_scope: str = "trace",
    ):
        if isinstance(features, FeatureSet):
            features = [features]
        features = list(features)
        if not features:
            raise ValueError("StreamingWindowDataset needs >= 1 FeatureSet")
        if dedup_scope not in ("trace", "global"):
            raise ValueError(
                f"dedup_scope must be 'trace' or 'global', got {dedup_scope!r}"
            )
        stride = stride or window
        has_labels = features[0].labels is not None
        parts: List[_StreamPart] = []
        for fs in features:
            if (fs.labels is not None) != has_labels:
                raise ValueError(
                    "all feature sets of one dataset must agree on labels"
                )
            inputs = {
                k: window_view(getattr(fs, k), window, stride)
                for k in _INPUT_KEYS
            }
            labels = None
            if has_labels:
                labels = {
                    k: window_view(fs.labels[k], window, stride)
                    for k in _LABEL_KEYS
                }
            parts.append(_StreamPart(inputs=inputs, labels=labels))
        # geometry check BEFORE the dedup pass: views are free, hashing a
        # multi-million-window corpus is not
        w_effs = {p.inputs["opcode"].shape[1] for p in parts}
        if len(w_effs) != 1:
            raise ValueError(
                f"feature sets produce mixed effective windows "
                f"{sorted(w_effs)}: every trace of one dataset must share a "
                "window geometry (the jitted train step compiles per "
                "geometry)"
            )
        keeps: List[np.ndarray] = []
        reservoir: set = set()
        for part in parts:
            if dedup:
                seen = reservoir if dedup_scope == "global" else set()
                keep = np.flatnonzero(
                    _dedup_mask(part.inputs, part.labels, seen=seen)
                )
            else:
                keep = np.arange(len(part.inputs["opcode"]), dtype=np.int64)
            keeps.append(keep.astype(np.int64))
        self._parts = parts
        # flat kept-window index -> (part, local window) lookup: O(windows)
        # *integers*, the only per-window state the streaming path keeps
        self._part_id = np.concatenate(
            [np.full(len(k), i, np.int32) for i, k in enumerate(keeps)]
        )
        self._local = np.concatenate(keeps)
        self.num_dropped = (
            sum(len(p.inputs["opcode"]) for p in parts) - len(self._local)
        )

    def __len__(self) -> int:
        return len(self._local)

    @property
    def window(self) -> int:
        return self._parts[0].inputs["opcode"].shape[1]

    @property
    def has_labels(self) -> bool:
        return self._parts[0].labels is not None

    def _gather_key(
        self, views: List[np.ndarray], part_id: np.ndarray, local: np.ndarray
    ) -> np.ndarray:
        if len(views) == 1:
            return views[0][local]
        v0 = views[0]
        out = np.empty((len(part_id),) + v0.shape[1:], dtype=v0.dtype)
        for p in np.unique(part_id):
            m = part_id == p
            out[m] = views[p][local[m]]
        return out

    def gather(self, idx: np.ndarray) -> Dict[str, np.ndarray]:
        """Materialize the windows at kept positions ``idx`` — the only
        copy the streaming path ever makes (one batch at a time)."""
        part_id = self._part_id[idx]
        local = self._local[idx]
        out = {
            k: self._gather_key(
                [p.inputs[k] for p in self._parts], part_id, local
            )
            for k in _INPUT_KEYS
        }
        if self.has_labels:
            out["labels"] = {
                k: self._gather_key(
                    [p.labels[k] for p in self._parts], part_id, local
                )
                for k in _LABEL_KEYS
            }
        return out

    def batches(
        self,
        batch_size: int,
        rng: Optional[np.random.Generator] = None,
        drop_last: bool = True,
    ) -> Iterator[Dict[str, np.ndarray]]:
        """Same contract — and bit-identical batch stream for the same
        ``rng`` state — as ``WindowDataset.batches``, materializing only
        O(batch) windows via per-batch gather."""
        n = len(self)
        order = np.arange(n)
        if rng is not None:
            rng.shuffle(order)
        stop = n - (n % batch_size) if drop_last else n
        for lo in range(0, stop, batch_size):
            with span("feed.gather"):
                out = self.gather(order[lo : lo + batch_size])
            yield out

    def subsample(self, n: int, seed: int = 0) -> "StreamingWindowDataset":
        """Uniform window subsample — same selection as
        ``WindowDataset.subsample`` (identical rng draw over identical
        length), but O(indices): only the kept-index lookup shrinks, the
        zero-copy views are shared with the parent."""
        if n >= len(self):
            return self
        idx = np.random.default_rng(seed).choice(len(self), size=n, replace=False)
        out = object.__new__(StreamingWindowDataset)
        out._parts = self._parts
        out._part_id = self._part_id[idx]
        out._local = self._local[idx]
        out.num_dropped = self.num_dropped
        return out

    def materialize(self) -> WindowDataset:
        """Copy every kept window into a ``WindowDataset`` (small runs and
        equivalence tests; defeats the purpose at scale)."""
        full = self.gather(np.arange(len(self)))
        return WindowDataset(
            inputs={k: full[k] for k in _INPUT_KEYS},
            labels=full.get("labels"),
        )


def concat_datasets(parts: Sequence[WindowDataset]) -> WindowDataset:
    inputs = {
        k: np.concatenate([p.inputs[k] for p in parts]) for k in _INPUT_KEYS
    }
    labels = None
    if parts[0].labels is not None:
        labels = {
            k: np.concatenate([p.labels[k] for p in parts]) for k in _LABEL_KEYS
        }
    return WindowDataset(inputs=inputs, labels=labels)
