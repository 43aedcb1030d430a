"""Persistent staging arena for checkpoint serialization (paper
§4.1/§4.3, DataStates-LLM's lazy reusable pinned buffers; DESIGN.md §6,
plus the §7 read-staging rules).

The naive serialize path re-allocates a fresh host copy of every tensor
on every ``save()`` — per-leaf ``np.ascontiguousarray`` churn that the
paper's pinned double-buffered staging eliminates. A
:class:`SerializeArena` owns ONE page-aligned host buffer sized to the
checkpoint stream and keyed by the state's structure
(treedef × dtypes × shapes):

  * the FIRST save lays out the stream and allocates the buffer;
  * STEADY-STATE saves copy device→arena in place — zero Python-side
    allocation, one memcpy per leaf, stable buffer identity (so the
    writer's O_DIRECT staging reads from page-aligned memory every
    time);
  * a shape/structure change (or :meth:`invalidate`, e.g. after buffer
    donation hands the arrays' storage back to XLA) re-lays-out, and
    re-allocates ONLY if the new stream is larger than the capacity.

The arena also stages RESTORES (DESIGN.md §7): :meth:`read_buffer`
hands out a second reusable page-aligned buffer that the parallel
restore path reads shard spans into, and ``deserialize`` then carves
zero-copy numpy views out of it — a steady-state load allocates
nothing. The read staging is a SEPARATE backing allocation from the
serialize staging, so an overlapped async save can never scribble over
a load in progress (or vice versa).

Lifetime rules (DESIGN.md §6/§7): an arena must not be refilled while a
previous save is still reading it. The engine's single helper thread
and ``PipelinedCheckpointer``'s one-worker queue serialize saves, so
overlapped (async) checkpointing reuses one arena safely; concurrent
``save()`` calls on one checkpointer need one arena each. Arrays
deserialized from :meth:`read_buffer` are views into it — valid until
the NEXT load on the same arena; copy them (``jnp.array`` /
``np.array``) to retain past that.
"""
from __future__ import annotations

import threading
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro import obs
from repro.core.serializer import (Manifest, TensorRecord, _path_str,
                                   portable_view, store_dtype)
from repro.core.writer import aligned_buffer

PAGE = 4096

#: dtypes the device-dirty snapshot path handles (the Pallas pack kernel
#: is bit-preserving for these at scale=1; everything else host-compares)
_DEV_DTYPES = ("float32", "bfloat16", "float16")

#: how far the chunked fill's device-to-host transfers may run ahead of
#: its copy into the arena (DESIGN.md §10): beside the arena, the host
#: holds at most this many bytes of the state, or one leaf where a leaf
#: is larger. The state is released only once its last leaf has landed,
#: so what the look-ahead does not cover is copied into the arena while
#: the train loop waits.
LOOKAHEAD_BYTES = 2 << 30


def _host_array(leaf) -> np.ndarray:
    """Device→host view of one leaf in the shared on-stream layout
    (serializer.portable_view), ndim >= 1. No copy unless the source is
    non-contiguous or lives on an accelerator."""
    return np.atleast_1d(portable_view(np.asarray(leaf)))


def _host_bytes(leaf) -> np.ndarray:
    """One leaf's stream bytes on the host, whole, as flat uint8 (span
    ``snapshot.xfer``: the blocking device-to-host transfer, or the wait
    for one already under way)."""
    with obs.span("snapshot.xfer"):
        return _host_array(leaf).reshape(-1).view(np.uint8)


def _own(x):
    """A new jax array over ``x``'s device buffers, one new single-device
    array per shard: jax caches a transfer's host copy on the array
    objects it went through, so a pull through these leaves no copy on
    ``x`` (which the caller and the save may hold until commit)."""
    shards = [jax.make_array_from_single_device_arrays(
        s.data.shape, jax.sharding.SingleDeviceSharding(s.device), [s.data])
        for s in x.addressable_shards]
    return jax.make_array_from_single_device_arrays(x.shape, x.sharding,
                                                    shards)


class _Pull:
    """The whole-leaf pull of a chunked fill (DESIGN.md §10; a device
    slice per piece would queue behind the train steps). Once the state
    is ready (span ``snapshot.ready``), device-to-host transfers start in
    stream order up to :data:`LOOKAHEAD_BYTES` ahead of the leaf being
    copied into the arena, and each leaf's host bytes are dropped once
    copied (:meth:`drop`). ``release`` is called as soon as the last leaf
    has landed: from there on nothing reads the leaves. Host leaves are
    copied, so a change made to them after the release is not saved."""

    def __init__(self, leaves, sizes: Sequence[int],
                 release: Optional[Callable[[], None]]):
        self._src = [x for _path, x in leaves]
        self._sizes = list(sizes)
        self._host: List[Optional[np.ndarray]] = [None] * len(self._src)
        self._started = 0          # leaves whose transfer has started
        self._ahead = 0            # their bytes not yet dropped
        self._release = release
        with obs.span("snapshot.ready"):
            jax.block_until_ready([x for x in self._src
                                   if isinstance(x, jax.Array)])

    def _start(self, i: int):
        n = len(self._src)
        while self._started < n and (
                self._started <= i or self._ahead
                + self._sizes[self._started] <= LOOKAHEAD_BYTES):
            j = self._started
            if isinstance(self._src[j], jax.Array):
                self._src[j] = _own(self._src[j])
                self._src[j].copy_to_host_async()
            self._ahead += self._sizes[j]
            self._started += 1

    def _land(self, j: int) -> np.ndarray:
        if self._host[j] is None:
            x, self._src[j] = self._src[j], None
            b = _host_bytes(x)
            self._host[j] = b if isinstance(x, jax.Array) else b.copy()
        return self._host[j]

    def take(self, i: int) -> np.ndarray:
        """Leaf ``i``'s stream bytes, as flat uint8."""
        self._start(i)
        if self._started == len(self._src):
            for j in range(i, len(self._src)):
                self._land(j)
            self.finish()
        return self._land(i)

    def drop(self, i: int):
        self._host[i] = None
        self._ahead -= self._sizes[i]

    def finish(self):
        """Call ``release`` once, if it has not been called yet."""
        release, self._release = self._release, None
        if release is not None:
            release()


class SnapshotProgress:
    """Byte watermark of an in-flight chunked device→arena snapshot.

    The fill worker ``advance()``s the watermark as each piece lands (in
    stream order, so a single monotonic counter is the whole "which
    chunks are filled" state); gated writer segments ``wait_until()``
    their bytes are covered before consuming them. A fill failure parks
    the exception here and re-raises at EVERY wait site — writers abort,
    the save raises, and the engine never reaches COMMIT (the §10
    crash-safety rule)."""

    def __init__(self, total: int, chunk_bytes: int):
        self.total = int(total)
        self.chunk_bytes = max(int(chunk_bytes), 1)
        self.n_chunks = max(1, -(-self.total // self.chunk_bytes))
        #: fill wall time, stamped when the fill worker finishes
        self.seconds = 0.0
        self._cond = threading.Condition()
        self._filled = 0
        self._exc: Optional[BaseException] = None
        self._done = False

    @property
    def filled(self) -> int:
        return self._filled

    @property
    def done(self) -> bool:
        return self._done

    @property
    def failed(self) -> bool:
        return self._exc is not None

    def advance(self, watermark: int):
        """Raise the filled-bytes watermark (monotonic; stream order)."""
        with self._cond:
            if watermark > self._filled:
                self._filled = int(watermark)
                self._cond.notify_all()

    def finish(self):
        with self._cond:
            self._filled = self.total
            self._done = True
            self._cond.notify_all()

    def fail(self, exc: BaseException):
        with self._cond:
            self._exc = exc
            self._done = True
            self._cond.notify_all()

    def wait_until(self, watermark: int):
        """Block until ``watermark`` stream bytes are staged; re-raises
        the fill worker's exception if the snapshot died."""
        watermark = min(int(watermark), self.total)
        with self._cond:
            while (self._filled < watermark and self._exc is None
                   and not self._done):
                self._cond.wait()
            if self._exc is not None:
                raise self._exc

    def wait_done(self):
        """Block until the whole snapshot landed (or failed)."""
        with self._cond:
            while not self._done:
                self._cond.wait()
            if self._exc is not None:
                raise self._exc


class SerializeArena:
    """Reusable page-aligned host staging buffer for one checkpoint
    stream. See module docstring for the lifecycle."""

    def __init__(self, alignment: int = PAGE):
        self.alignment = alignment
        self._key: Optional[tuple] = None
        self._raw: Optional[np.ndarray] = None   # oversized backing store
        self._mv: Optional[memoryview] = None    # aligned capacity window
        self._records: Optional[list] = None     # cached TensorRecords
        self._buffers: Optional[List[np.ndarray]] = None  # per-record views
        self._treedef_str: Optional[str] = None
        self._total = 0
        self.capacity = 0
        # read-staging twin (restore path; separate backing, see
        # module docstring)
        self._read_raw: Optional[np.ndarray] = None
        self._read_mv: Optional[memoryview] = None
        self.read_capacity = 0
        # --- observability (SaveStats / benchmarks read these) ---
        self.n_alloc = 0        # backing-buffer allocations
        self.n_layout = 0       # stream layouts (key misses)
        self.n_reuse = 0        # steady-state fills into cached layout
        self.last_reused = False
        self.n_read_alloc = 0   # read-staging allocations
        self.n_read_reuse = 0   # loads served from the cached buffer
        # --- dirty-range tracking (delta checkpoints, DESIGN.md §9) ---
        #: stream-coordinate (offset, length) spans where the LAST
        #: serialize differed from the resident previous image; None
        #: when tracking was off or there was no valid baseline (layout
        #: miss / first fill). An empty list means "nothing changed".
        self.last_dirty: Optional[List[Tuple[int, int]]] = None
        self.last_dirty_bytes: Optional[int] = None
        # --- device-dirty snapshots (DESIGN.md §10) ---
        #: per-record device-resident packed previous images (kernel
        #: outputs — safe from train-step donation) for the
        #: ckpt_pack_dirty change-mask compare; None entries fall back
        #: to the host copy+compare path
        self._dev_prev: Optional[List[Any]] = None
        #: True iff the resident host image is a COMPLETE copy of the
        #: last fill (the per-chunk invariant: a fill in flight or died
        #: mid-stream leaves this False, which disables both dirty
        #: tracking and device-mask clean-block skipping next save)
        self._image_valid = False
        #: bytes that crossed device→host during the last fill (masks +
        #: gathered dirty blocks on the device path; everything on the
        #: host path) — the PCIe-traffic figure fig_snapshot reports
        self.last_d2h_bytes = 0

    # ------------------------------------------------------------ state
    def invalidate(self):
        """Drop the cached layout (NOT the backing memory). Call when the
        cached views may alias freed storage — e.g. after the train step
        donated the state's buffers back to XLA."""
        self._key = None
        self._records = None
        self._buffers = None
        self._dev_prev = None
        self._image_valid = False

    def _ensure_capacity(self, total: int):
        if self._raw is None or total > self.capacity:
            size = max(total, 1)
            self._mv = aligned_buffer(size, self.alignment)
            self._raw = self._mv.obj         # backing ndarray (identity)
            self.capacity = size
            self.n_alloc += 1

    # ------------------------------------------------------ read staging
    def read_buffer(self, nbytes: int) -> memoryview:
        """Reusable page-aligned READ-staging window of ``nbytes``
        (restore path): the first load allocates, steady-state loads
        reuse; contents are undefined until the caller fills them.
        Separate backing from the serialize staging — refilling one
        never corrupts the other. Lifetime rule: views carved out of
        this buffer (zero-copy ``deserialize``) are valid until the
        next ``read_buffer`` call that grows it OR the next load that
        refills it."""
        if self._read_raw is None or nbytes > self.read_capacity:
            size = max(nbytes, 1)
            self._read_mv = aligned_buffer(size, self.alignment)
            self._read_raw = self._read_mv.obj
            self.read_capacity = size
            self.n_read_alloc += 1
        else:
            self.n_read_reuse += 1
        return self._read_mv[:nbytes]

    def read_buffer_id(self) -> Optional[int]:
        """Identity of the read-staging allocation (tests assert reuse)."""
        return id(self._read_raw) if self._read_raw is not None else None

    # ----------------------------------------------------------- layout
    @staticmethod
    def _signature(leaves, treedef) -> tuple:
        sig = []
        for _path, leaf in leaves:
            dt = str(leaf.dtype) if hasattr(leaf, "dtype") else \
                str(np.asarray(leaf).dtype)
            sig.append((dt, tuple(np.shape(leaf))))
        return (treedef, tuple(sig))

    def _layout(self, leaves, treedef, key):
        """Key miss: compute records/offsets from METADATA only (no
        device transfer), grow the buffer if needed, carve per-record
        views."""
        records, metas = [], []
        offset = 0
        for path, leaf in leaves:
            name = _path_str(path)
            orig_dtype = str(leaf.dtype) if hasattr(leaf, "dtype") \
                else str(np.asarray(leaf).dtype)
            shape = tuple(np.shape(leaf))
            sdt = store_dtype(orig_dtype)
            count = int(np.prod(shape, dtype=np.int64)) if shape else 1
            nbytes = count * sdt.itemsize
            records.append(TensorRecord(name, orig_dtype, shape, offset,
                                        nbytes))
            metas.append((offset, count, sdt, shape))
            offset += nbytes
        self._ensure_capacity(offset)
        buffers = []
        for off, count, sdt, shape in metas:
            view = np.frombuffer(self._mv, dtype=sdt, count=count,
                                 offset=off)
            buffers.append(view.reshape(shape) if shape else view)
        self._key = key
        self._records = records
        self._buffers = buffers
        self._treedef_str = str(treedef)
        self._total = offset
        self._dev_prev = None
        self._image_valid = False
        self.n_layout += 1

    # -------------------------------------------------------- serialize
    def _prepare(self, leaves, treedef):
        """Key check + (on miss) metadata-only layout; sets last_reused."""
        key = self._signature(leaves, treedef)
        if key != self._key or self._buffers is None:
            self._layout(leaves, treedef, key)
            self.last_reused = False
        else:
            self.n_reuse += 1
            self.last_reused = True

    @staticmethod
    def _device_eligible(rec, dirty_block: int) -> bool:
        """Records the ckpt_pack_dirty kernel can snapshot: float dtypes
        whose per-mask-block element count is a whole multiple of the
        8x128 vreg tile, and at least one block long."""
        if rec.dtype not in _DEV_DTYPES or rec.nbytes < dirty_block:
            return False
        isz = store_dtype(rec.dtype).itemsize
        return dirty_block % isz == 0 and (dirty_block // isz) % 1024 == 0

    def _fill_record_device(self, leaf, dst, rec, prev, dirty,
                            dirty_block: int):
        """Device-mask snapshot of one record: the Pallas kernel compares
        the packed image against ``prev`` on device, and only dirty
        blocks (plus the tiny mask) cross PCIe. Clean blocks are skipped
        entirely — valid because the resident arena bytes equal the
        previous packed image (``_image_valid``) and the pack is
        bit-preserving. Returns (new prev, d2h bytes moved)."""
        from repro.core.delta import mask_to_spans
        from repro.kernels import ops
        elems = dirty_block // store_dtype(rec.dtype).itemsize
        packed, _amax, mask = ops.ckpt_pack_dirty(leaf, prev, block=elems)
        with obs.span("snapshot.xfer"):
            mask_h = np.asarray(mask)
        d2h = mask_h.nbytes
        # the image pads to whole grid steps; pad blocks are always clean
        idx = np.flatnonzero(mask_h[:-(-rec.nbytes // dirty_block)])
        if idx.size:
            with obs.span("snapshot.xfer"):
                rows = np.asarray(packed[idx])        # gather: one D2H
            rows8 = np.ascontiguousarray(portable_view(rows)) \
                .view(np.uint8).reshape(idx.size, dirty_block)
            d2h += rows8.nbytes
            dst8 = dst.reshape(-1).view(np.uint8)
            with obs.span("snapshot.copy"):
                for k, b in enumerate(idx.tolist()):
                    lo = b * dirty_block
                    hi = min(lo + dirty_block, rec.nbytes)
                    dst8[lo:hi] = rows8[k, :hi - lo]
        if dirty is not None:
            dirty.extend((rec.offset + off, length) for off, length
                         in mask_to_spans(mask_h, dirty_block, rec.nbytes))
        return packed, d2h

    def _fill(self, leaves, *, track_dirty: bool, dirty_block: int,
              device_dirty: bool = False,
              progress: Optional[SnapshotProgress] = None,
              chunk_bytes: int = 0,
              release: Optional[Callable[[], None]] = None):
        """Copy ``leaves`` into the laid-out arena (device→host), piece
        by piece when chunked. Dirty compare runs per piece BEFORE the
        copy-in overwrites the resident image; spans never cross record
        boundaries (adjacent pieces of one record merge).

        A chunked fill without device masks pulls whole leaves a bounded
        number of bytes ahead of its copy (:class:`_Pull`) and calls
        ``release`` once the last leaf has landed: from there on nothing
        reads ``leaves``, and the caller may donate them while the last
        pieces are copied into the arena. Other fills take each record
        whole as they reach it and call ``release`` at the end."""
        from repro.core.delta import dirty_byte_spans
        prev_valid = self.last_reused and self._image_valid
        self._image_valid = False
        dirty: Optional[list] = [] if (track_dirty and prev_valid) else None
        n = len(self._records)
        old_prev = (self._dev_prev
                    if (device_dirty and prev_valid
                        and self._dev_prev is not None
                        and len(self._dev_prev) == n) else None)
        new_prev: Optional[list] = [None] * n if device_dirty else None
        piece = 0
        if progress is not None and chunk_bytes > 0:
            piece = max(chunk_bytes - chunk_bytes % dirty_block,
                        dirty_block)
        d2h = 0
        pull = None
        if progress is not None and not device_dirty:
            pull = _Pull(leaves, [r.nbytes for r in self._records], release)
        for i, ((_path, leaf), dst, rec) in enumerate(
                zip(leaves, self._buffers, self._records)):
            end = rec.offset + rec.nbytes
            if dst.size == 0:
                if progress is not None:
                    progress.advance(end)
                continue
            if old_prev is not None and old_prev[i] is not None \
                    and self._device_eligible(rec, dirty_block):
                new_prev[i], nb = self._fill_record_device(
                    leaf, dst, rec, old_prev[i], dirty, dirty_block)
                # drop each old image as its successor lands, so the
                # device holds one image of the state, not two
                old_prev[i] = None
                d2h += nb
                if progress is not None:
                    progress.advance(end)
                continue
            # host path: piece-granular compare+copy
            src = pull.take(i) if pull is not None else _host_bytes(leaf)
            dst8 = dst.reshape(-1).view(np.uint8)
            step = piece if piece else rec.nbytes
            rec_spans: list = []
            lo = 0
            while lo < rec.nbytes:
                hi = min(lo + step, rec.nbytes)
                pb = src[lo:hi]
                if pb.size != hi - lo:
                    raise ValueError(
                        f"record {rec.name!r}: leaf yields {pb.size} "
                        f"bytes for [{lo},{hi}) of {rec.nbytes}")
                if dirty is not None:
                    for off, length in dirty_byte_spans(dst8[lo:hi], pb,
                                                        dirty_block):
                        off += lo
                        if rec_spans and sum(rec_spans[-1]) == off:
                            rec_spans[-1] = (rec_spans[-1][0],
                                             rec_spans[-1][1] + length)
                        else:
                            rec_spans.append((off, length))
                with obs.span("snapshot.copy"):
                    dst8[lo:hi] = pb
                if progress is not None:
                    progress.advance(rec.offset + hi)
                lo = hi
            if dirty is not None:
                dirty.extend((rec.offset + off, length)
                             for off, length in rec_spans)
            if pull is not None:
                src = pb = None        # the last views of its bytes
                pull.drop(i)
            d2h += rec.nbytes
            if device_dirty and self._device_eligible(rec, dirty_block):
                # seed the device baseline so the NEXT fill can mask
                from repro.kernels import ops
                elems = dirty_block // store_dtype(rec.dtype).itemsize
                new_prev[i] = ops.pack_blocks(leaf, block=elems)
        self._dev_prev = new_prev
        self.last_dirty = dirty
        self.last_dirty_bytes = (sum(ln for _, ln in dirty)
                                 if dirty is not None else None)
        self.last_d2h_bytes = d2h
        obs.count("snapshot.bytes", d2h)
        self._image_valid = True
        if pull is not None:
            pull.finish()
        elif release is not None:
            release()

    def serialize(self, leaves, treedef, track_dirty: bool = False,
                  dirty_block: int = 4096, device_dirty: bool = False):
        """Fill the arena from ``leaves`` and return
        ``(Manifest, buffers)`` with the serializer's exact contract:
        ``buffers[i]`` holds record *i*'s bytes (views into the arena).

        With ``track_dirty``, each record's incoming bytes are compared
        against the RESIDENT previous image (blockwise, BEFORE the
        copy-in overwrites it) and the coalesced dirty spans land in
        ``self.last_dirty`` in stream coordinates — the input to a delta
        checkpoint (DESIGN.md §9). Tracking needs a valid baseline:
        on a layout miss (first fill / shape change / ``invalidate``)
        ``last_dirty`` is None and the caller must write a keyframe.

        With ``device_dirty`` (DESIGN.md §10), float records carry a
        device-resident packed previous image and the ckpt_pack_dirty
        kernel's change mask decides which blocks cross PCIe — clean
        blocks are never transferred; the host compare above remains the
        fallback (and produces identical spans)."""
        self._prepare(leaves, treedef)
        self._fill(leaves, track_dirty=track_dirty,
                   dirty_block=dirty_block, device_dirty=device_dirty)
        manifest = Manifest(self._records, self._total,
                            treedef=self._treedef_str)
        return manifest, list(self._buffers)

    def begin_snapshot(self, leaves, treedef, chunk_bytes: int, *,
                       track_dirty: bool = False, dirty_block: int = 4096,
                       device_dirty: bool = False, step: int = -1,
                       release: Optional[Callable[[], None]] = None):
        """Chunked-snapshot entry (DESIGN.md §10): lay out the stream
        (metadata only — no device transfer) and return
        ``(manifest, buffers, progress, fill)`` WITHOUT copying a byte.

        ``fill()`` — run it on a snapshot worker thread — streams the
        device→arena copy in ``chunk_bytes`` pieces, advancing
        ``progress`` as each lands so gated writer segments can consume
        chunks while later tensors are still leaving the device.
        ``fill`` never raises: failures land in ``progress`` and
        re-raise at every ``wait_*`` site, which is how a mid-snapshot
        death aborts the writers before COMMIT. The fill is the span
        ``snapshot.fill`` (tagged ``step``), whose seconds become
        ``progress.seconds``. ``release`` is called once the fill no
        longer reads ``leaves`` (:meth:`_fill`)."""
        self._prepare(leaves, treedef)
        progress = SnapshotProgress(self._total, chunk_bytes)
        manifest = Manifest(self._records, self._total,
                            treedef=self._treedef_str)
        buffers = list(self._buffers)

        def fill():
            sp = obs.span("snapshot.fill", step=step)
            try:
                with sp:
                    self._fill(leaves, track_dirty=track_dirty,
                               dirty_block=dirty_block,
                               device_dirty=device_dirty, progress=progress,
                               chunk_bytes=chunk_bytes, release=release)
            except BaseException as exc:   # noqa: BLE001 — parked, re-raised
                progress.seconds = sp.seconds
                progress.fail(exc)
            else:
                progress.seconds = sp.seconds
                progress.finish()

        return manifest, buffers, progress, fill

    # ------------------------------------------------------------ intro
    @property
    def nbytes(self) -> int:
        return self.capacity if self._raw is not None else 0

    def buffer_id(self) -> Optional[int]:
        """Identity of the backing allocation (stable across steady-state
        saves; benchmarks/tests assert reuse with this)."""
        return id(self._raw) if self._raw is not None else None

    def __repr__(self):
        return (f"SerializeArena(capacity={self.capacity}, "
                f"alloc={self.n_alloc}, layout={self.n_layout}, "
                f"reuse={self.n_reuse})")
