"""Checkpoint-state serialization (paper §2.1.3).

A checkpoint state is a pytree of arrays plus JSON-able extras (step, rng,
data-iterator state, LR schedule). Serialization produces:

  * an ordered sequence of per-tensor byte segments (the "sequence of
    writes of serialized tensors" the paper describes), and
  * a manifest (tensor metadata: path, dtype, shape, offset, nbytes)
    providing portability and simple loading.

``ByteStreamView`` exposes the concatenated stream for BYTE-GRANULARITY
partitioning (§4.2): a writer's extent may begin/end mid-tensor; the view
yields zero-copy memoryview slices in stream order.
"""
from __future__ import annotations

import json
import zlib
from bisect import bisect_right
from dataclasses import dataclass, field
from typing import Any, Iterator, List, Optional, Sequence, Tuple

import jax
import numpy as np

_DTYPE_NAMES = {"bfloat16": "bfloat16"}  # jax-only dtype passthrough


def _path_str(path) -> str:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "idx"):
            out.append(str(p.idx))
        else:
            out.append(str(p))
    return "/".join(out)


@dataclass(frozen=True)
class TensorRecord:
    name: str
    dtype: str
    shape: Tuple[int, ...]
    offset: int           # byte offset in the checkpoint stream
    nbytes: int


@dataclass
class Manifest:
    records: List[TensorRecord]
    total_bytes: int
    extras: dict = field(default_factory=dict)
    treedef: Optional[str] = None     # printable treedef for debugging

    def to_json(self) -> str:
        return json.dumps({
            "records": [vars(r) for r in self.records],
            "total_bytes": self.total_bytes,
            "extras": self.extras,
            "treedef": self.treedef,
        })

    @classmethod
    def from_json(cls, s: str) -> "Manifest":
        d = json.loads(s)
        recs = [TensorRecord(r["name"], r["dtype"], tuple(r["shape"]),
                             r["offset"], r["nbytes"])
                for r in d["records"]]
        return cls(recs, d["total_bytes"], d.get("extras", {}),
                   d.get("treedef"))


def portable_view(arr: np.ndarray) -> np.ndarray:
    """THE on-stream byte-layout rule: bf16 is bit-cast to uint16 for a
    portable layout. Shared by the allocate-per-save path below and the
    arena path (repro.core.arena) — the two must stay byte-identical."""
    if arr.dtype == np.dtype("V2") or str(arr.dtype) == "bfloat16":
        return arr.view(np.uint16)
    return arr


def store_dtype(dtype_str: str) -> np.dtype:
    """On-stream numpy dtype for a manifest dtype string (the
    dtype-string form of :func:`portable_view`)."""
    if dtype_str == "bfloat16":
        return np.dtype(np.uint16)
    return np.dtype(dtype_str)


def _to_numpy(leaf) -> np.ndarray:
    """Device→host transfer ('read GPU tensors into pinned CPU memory',
    §4.3)."""
    arr = np.asarray(leaf) if not hasattr(leaf, "addressable_data") \
        else np.asarray(leaf)
    return np.ascontiguousarray(portable_view(arr))


def serialize(state, arena=None, track_dirty: bool = False,
              dirty_block: int = 4096, device_dirty: bool = False
              ) -> Tuple[Manifest, List[np.ndarray]]:
    """Flatten a checkpoint state into (manifest, ordered host buffers).

    With ``arena`` (a :class:`repro.core.arena.SerializeArena`), buffers
    are views into the arena's persistent page-aligned staging memory:
    the first save allocates, steady-state saves copy device→arena in
    place with zero Python-side allocation (DESIGN.md §6). Without it,
    the original allocate-per-save path runs (one fresh host copy per
    leaf).

    ``track_dirty`` (arena path only) compares incoming bytes against
    the arena's resident previous image during the copy and records the
    dirty spans in ``arena.last_dirty`` — the input to an incremental
    delta checkpoint (DESIGN.md §9)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(state)
    if arena is not None:
        return arena.serialize(leaves, treedef, track_dirty=track_dirty,
                               dirty_block=dirty_block,
                               device_dirty=device_dirty)
    records, buffers = [], []
    offset = 0
    for path, leaf in leaves:
        name = _path_str(path)
        orig_dtype = str(leaf.dtype) if hasattr(leaf, "dtype") else "float32"
        arr = _to_numpy(leaf)
        rec = TensorRecord(name, orig_dtype, tuple(np.shape(leaf)),
                           offset, arr.nbytes)
        records.append(rec)
        buffers.append(arr)
        offset += arr.nbytes
    return Manifest(records, offset, treedef=str(treedef)), buffers


def begin_snapshot(state, arena, chunk_bytes: int, *,
                   track_dirty: bool = False, dirty_block: int = 4096,
                   device_dirty: bool = False, step: int = -1,
                   release=None):
    """Chunked-snapshot variant of :func:`serialize` (DESIGN.md §10):
    lays out the stream against ``arena`` without copying and returns
    ``(manifest, buffers, progress, fill)`` — the caller runs ``fill``
    on a snapshot worker and gates writer segments on ``progress``.
    Arena-only: the allocate-per-save path has no resident image to
    fill piecewise. ``release`` is called once the fill no longer reads
    ``state``."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(state)
    return arena.begin_snapshot(leaves, treedef, chunk_bytes,
                                track_dirty=track_dirty,
                                dirty_block=dirty_block,
                                device_dirty=device_dirty, step=step,
                                release=release)


def decode_record(rec: TensorRecord, raw) -> np.ndarray:
    """Rebuild ONE tensor from its raw bytes: dtype decode (bf16 is
    stored bit-cast to uint16; quantized records carry "int8|<orig>")
    plus the reshape guard for synthetic ``#`` records (e.g. "#scale")
    whose element count differs from the original tensor shape."""
    dtype = rec.dtype.split("|")[0]
    if dtype == "bfloat16":
        import ml_dtypes
        arr = np.frombuffer(raw, np.uint16).view(ml_dtypes.bfloat16)
    else:
        arr = np.frombuffer(raw, np.dtype(dtype))
    if rec.name.find("#") < 0 or arr.size == int(np.prod(rec.shape)):
        return arr.reshape(rec.shape)
    return arr


def deserialize(manifest: Manifest, data: bytes | bytearray | memoryview,
                like=None):
    """Rebuild arrays from the checkpoint stream. If ``like`` (a pytree of
    the same structure) is given, returns that structure; otherwise a flat
    {name: array} dict."""
    out = {}
    mv = memoryview(data)
    for rec in manifest.records:
        out[rec.name] = decode_record(rec, mv[rec.offset:rec.offset
                                              + rec.nbytes])
    if like is not None:
        leaves, treedef = jax.tree_util.tree_flatten_with_path(like)
        new_leaves = [out[_path_str(p)] for p, _ in leaves]
        return jax.tree_util.tree_unflatten(treedef, new_leaves)
    return out


def tensor_spans(records: Sequence[TensorRecord],
                 extents) -> dict:
    """Global index for the sharded layout (DESIGN.md §5): map every
    tensor to the ``[shard_index, offset_in_shard, length]`` spans that
    hold its bytes. Byte-granularity extents split tensors mid-stream,
    so a tensor may span several shards; a rank-elastic reader uses this
    index to fetch exactly the byte ranges it needs, from any number of
    shards, regardless of the writer topology that produced them.

    O(R log E + S) where S is the emitted span count: extents are
    disjoint and sorted by offset, so their END offsets are monotonic —
    bisect to the first extent that can overlap a record, then walk
    forward until the extents start past it."""
    exts = sorted(extents, key=lambda e: e.offset)
    ends = [e.offset + e.length for e in exts]
    index: dict = {}
    for rec in records:
        spans = []
        lo, hi = rec.offset, rec.offset + rec.nbytes
        # first extent with end > lo; everything before cannot overlap
        i = bisect_right(ends, lo)
        while i < len(exts) and exts[i].offset < hi:
            e = exts[i]
            if e.offset + e.length > lo:
                s, t = max(lo, e.offset), min(hi, e.offset + e.length)
                spans.append([e.shard_index, s - e.offset, t - s])
            i += 1
        index[rec.name] = spans
    return index


class ByteStreamView:
    """Zero-copy view of the ordered tensor buffers as one byte stream."""

    def __init__(self, buffers: Sequence[np.ndarray]):
        self._views = [memoryview(b).cast("B") for b in buffers]
        self._offsets = np.cumsum([0] + [v.nbytes for v in self._views])
        self.total = int(self._offsets[-1])

    def slices(self, start: int, length: int) -> Iterator[memoryview]:
        """Yield memoryview chunks covering [start, start+length)."""
        assert 0 <= start and start + length <= self.total
        end = start + length
        i = int(np.searchsorted(self._offsets, start, "right")) - 1
        while start < end and i < len(self._views):
            v = self._views[i]
            base = int(self._offsets[i])
            lo = start - base
            hi = min(end - base, v.nbytes)
            if hi > lo:
                yield v[lo:hi]
            start = base + hi
            i += 1

    def read(self, start: int, length: int) -> memoryview:
        """Materialize [start, start+length) into ONE preallocated
        buffer (no per-segment bytes() copies, no join). The returned
        memoryview compares equal to the corresponding bytes and feeds
        any buffer-protocol consumer; wrap in bytes() if an immutable
        copy is required."""
        out = bytearray(length)
        pos = 0
        for s in self.slices(start, length):
            out[pos:pos + s.nbytes] = s
            pos += s.nbytes
        return memoryview(out)

    def crc32(self, start: int = 0, length: Optional[int] = None) -> int:
        length = self.total - start if length is None else length
        c = 0
        for s in self.slices(start, length):
            c = zlib.crc32(s, c)
        return c
