"""Chunked device→arena snapshot pipeline (DESIGN.md §10).

Covers the three §10 contracts:
  * chunk-granular handoff — writers start before the snapshot ends,
    yet the bytes on disk are identical to a monolithic save;
  * crash safety — a snapshot that dies between chunk N and N+1 never
    reaches COMMIT, and the next save is clean;
  * snapshot-granular sync — ``wait_snapshot`` returns as soon as the
    device→arena copy lands, while the write is still in flight;
plus the device-side dirty-mask path: delta chains built from kernel
masks restore bit-exactly and move ~dirty bytes (not the stream) over
the device→host link, and the whole-leaf pull of the chunked fill, which
lets the state go once its last leaf has crossed and holds a bounded
number of its bytes on the host.
"""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import arena as arena_mod
from repro.core.arena import SerializeArena, SnapshotProgress
from repro.core.checkpointer import (FastPersistCheckpointer,
                                     FastPersistConfig, _GatedSegments)
from repro.core.engine import CheckpointEngine, CheckpointSpec
from repro.core.partition import Topology
from repro.core.serializer import ByteStreamView
from repro.core.writer import WriterConfig, write_stream


def _state(seed=0, kb=512):
    """~kb KiB of f32 params + a host-path scalar record."""
    k = jax.random.PRNGKey(seed)
    n = kb * 256                       # f32 elements
    return {
        "params": {"w": jax.random.normal(k, (n,), jnp.float32),
                   "b": jax.random.normal(k, (2048,), jnp.float32)},
        "step": jnp.int32(1),
    }


def _mutate(state, frac=0.01, seed=1):
    """Localized sparse update (the delta-friendly pattern: a training
    step touching a hot region): bump a contiguous ``frac`` window of w
    at a seeded offset, plus the scalar."""
    rng = np.random.default_rng(seed)
    w = np.asarray(state["params"]["w"]).copy()
    n = max(1, int(w.size * frac))
    off = int(rng.integers(0, max(1, w.size - n)))
    w[off:off + n] += 1.0
    return {
        "params": {"w": jnp.asarray(w), "b": state["params"]["b"]},
        "step": state["step"] + 1,
    }


def _cfg(**kw):
    kw.setdefault("strategy", "replica")
    kw.setdefault("topology", Topology(dp_degree=2))
    return FastPersistConfig(**kw)


def _assert_tree_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ----------------------------------------------------- SnapshotProgress
def test_progress_watermark_semantics():
    p = SnapshotProgress(total=10 << 20, chunk_bytes=1 << 20)
    assert p.n_chunks == 10 and p.filled == 0 and not p.done
    p.advance(5 << 20)
    p.advance(3 << 20)                 # stale watermark: monotonic
    assert p.filled == 5 << 20
    p.wait_until(4 << 20)              # already covered: returns
    p.finish()
    assert p.done and p.filled == p.total
    p.wait_until(p.total + 123)        # clamped to total
    assert SnapshotProgress(5, 2).n_chunks == 3
    assert SnapshotProgress(0, 1 << 20).n_chunks == 1


def test_progress_failure_reraises_at_every_wait_site():
    p = SnapshotProgress(total=1 << 20, chunk_bytes=1 << 20)
    boom = RuntimeError("snapshot died")
    p.fail(boom)
    assert p.failed and p.done
    with pytest.raises(RuntimeError, match="snapshot died"):
        p.wait_until(1)
    with pytest.raises(RuntimeError, match="snapshot died"):
        p.wait_done()


def test_gated_segments_block_until_covered():
    """A gated consumer only sees bytes the watermark covers, in order,
    and the producer's chunk cadence is what unblocks it."""
    buf = np.arange(1 << 16, dtype=np.uint8)
    view = ByteStreamView([buf])
    p = SnapshotProgress(total=buf.nbytes, chunk_bytes=1 << 12)
    got = bytearray()
    done = threading.Event()

    def consume():
        for seg in _GatedSegments(view, 0, buf.nbytes, p):
            got.extend(bytes(seg))
        done.set()

    t = threading.Thread(target=consume, daemon=True)
    t.start()
    for end in range(1 << 12, buf.nbytes + 1, 1 << 12):
        assert len(got) <= p.filled     # never reads past the watermark
        p.advance(end)
    p.finish()
    assert done.wait(10)
    t.join()
    assert bytes(got) == buf.tobytes()


def test_gated_write_stream_flushes_per_chunk(tmp_path):
    """write_stream + would_block(): when the watermark stalls, the
    writer submits the aligned bytes in hand instead of waiting for a
    full ``io_buffer_size`` fill — the on-disk submission count tracks
    the chunk cadence even though the whole stream fits in ONE staging
    buffer (the §10 early-flush rule)."""
    chunk = 256 << 10
    buf = np.frombuffer(bytes(range(256)) * (chunk * 4 // 256),
                        dtype=np.uint8).copy()
    view = ByteStreamView([buf])
    p = SnapshotProgress(total=buf.nbytes, chunk_bytes=chunk)
    gate = _GatedSegments(view, 0, buf.nbytes, p)
    path = str(tmp_path / "gated.bin")
    out = {}

    def write():
        out["stats"] = write_stream(path, gate, buf.nbytes, WriterConfig())

    t = threading.Thread(target=write, daemon=True)
    t.start()
    # lock-step: land one chunk, wait for the writer to consume it (the
    # gate's cursor reaches the watermark only once the piece is handed
    # over), so every inter-chunk gap really does stall the gate
    for end in range(chunk, buf.nbytes + 1, chunk):
        p.advance(end)
        for _ in range(2000):
            if gate._cursor >= min(end, buf.nbytes):
                break
            time.sleep(0.001)
        assert gate._cursor >= end, "writer never consumed the chunk"
    p.finish()
    t.join(timeout=30)
    assert not t.is_alive()
    st = out["stats"]
    # one submission per stalled chunk, not one giant buffered write
    assert st.n_writes >= 4, st
    with open(path, "rb") as f:
        assert f.read() == buf.tobytes()


# ------------------------------------------------- chunked == monolithic
def test_chunked_fill_matches_monolithic_bytes_and_spans():
    state = _state(kb=256)
    mono, chunked = SerializeArena(), SerializeArena()
    leaves, treedef = jax.tree_util.tree_flatten_with_path(state)
    m1, b1 = mono.serialize(leaves, treedef)
    man, bufs, progress, fill = chunked.begin_snapshot(
        leaves, treedef, chunk_bytes=64 << 10)
    fill()                              # inline: same thread is fine
    progress.wait_done()
    assert progress.done and progress.filled == man.total_bytes
    assert m1.total_bytes == man.total_bytes
    v1, v2 = ByteStreamView(b1), ByteStreamView(bufs)
    assert bytes(v1.read(0, v1.total)) == bytes(v2.read(0, v2.total))

    # dirty tracking through the chunked path == host compare
    state2 = _mutate(state)
    leaves2, _ = jax.tree_util.tree_flatten_with_path(state2)
    mono.serialize(leaves2, treedef, track_dirty=True)
    _, _, prog2, fill2 = chunked.begin_snapshot(
        leaves2, treedef, chunk_bytes=64 << 10, track_dirty=True)
    fill2()
    prog2.wait_done()
    assert chunked.last_dirty == mono.last_dirty
    assert chunked.last_dirty            # something actually changed


def test_chunked_roundtrip_engine():
    """End-to-end: chunked snapshot (several chunks) through the async
    engine, bit-exact load, chunk accounting in SaveStats."""
    state = _state(kb=8192)             # ~8.4 MB stream
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        spec = CheckpointSpec(directory=d, backend="fastpersist-pipelined",
                              fp=_cfg(snapshot_chunk_mb=1))
        with CheckpointEngine(spec) as eng:
            h = eng.save(state, 1, extras={"step": 1})
            stats = h.result()
            assert stats.snapshot_chunks >= 8
            assert stats.snapshot_seconds > 0.0
            loaded, man = eng.load(1, like=state)
            _assert_tree_equal(state, loaded)
            # writers report their gate wait separately from copy time
            assert all(w.source_wait_seconds >= 0.0
                       for w in stats.per_writer)


# ------------------------------------------------------- crash safety
def test_snapshot_death_between_chunks_never_commits(monkeypatch):
    """Kill the fill worker between chunk N and N+1: the save raises,
    COMMIT is never reached, latest_step is unchanged, and the NEXT
    save is clean (full keyframe, arena image rebuilt)."""
    state = _state(kb=1024)
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        spec = CheckpointSpec(directory=d, backend="fastpersist-pipelined",
                              fp=_cfg(snapshot_chunk_mb=1))
        with CheckpointEngine(spec) as eng:
            eng.save(state, 1).result()
            assert eng.latest_step() == 1

            real_advance = SnapshotProgress.advance
            calls = {"n": 0}

            def dying_advance(self, watermark):
                calls["n"] += 1
                if calls["n"] == 2:     # chunk 1 landed, chunk 2 dies
                    raise RuntimeError("D2H died mid-snapshot")
                return real_advance(self, watermark)

            monkeypatch.setattr(SnapshotProgress, "advance", dying_advance)
            h = eng.save(_mutate(state), 2)
            with pytest.raises(RuntimeError, match="died mid-snapshot"):
                h.result()
            # the engine ALSO surfaces the lost save at its sync point
            # (never swallow a failed checkpoint); drain it
            with pytest.raises(RuntimeError, match="died mid-snapshot"):
                eng.wait()
            monkeypatch.setattr(SnapshotProgress, "advance", real_advance)

            assert eng.latest_step() == 1          # no COMMIT for step 2
            with pytest.raises(FileNotFoundError):
                eng.load(2, like=state)
            # next save is clean and loadable
            state3 = _mutate(state, seed=3)
            eng.save(state3, 3).result()
            assert eng.latest_step() == 3
            loaded, _ = eng.load(3, like=state3)
            _assert_tree_equal(state3, loaded)


# ------------------------------------------- snapshot-granular sync point
def test_wait_snapshot_returns_before_commit(monkeypatch):
    """The §10 sync contract: once the snapshot lands, the main thread
    may proceed (donate buffers) while the WRITE is still in flight;
    wait()/result() remain the durability points."""
    import tempfile
    from repro.core import checkpointer as ckpt_mod
    release = threading.Event()
    real_ws = ckpt_mod.write_stream

    def gated_write_stream(path, segments, total, config, file_offset=0):
        segs = list(segments)           # drain the gate first (fill side)
        assert release.wait(30), "test writer never released"
        return real_ws(path, iter(segs), total, config,
                       file_offset=file_offset)

    monkeypatch.setattr(ckpt_mod, "write_stream", gated_write_stream)
    state = _state(kb=512)
    try:
        with tempfile.TemporaryDirectory() as d:
            spec = CheckpointSpec(directory=d,
                                  backend="fastpersist-pipelined",
                                  fp=_cfg(snapshot_chunk_mb=1))
            with CheckpointEngine(spec) as eng:
                h = eng.save(state, 1)
                h.wait_snapshot(timeout=30)
                assert h.snapshot_done() and not h.done()
                eng.wait_snapshot()     # engine-level: also returns now
                assert eng.stats.snapshot_stall_seconds >= 0.0
                assert not h.done()     # commit still pending
                release.set()
                h.result()
                assert eng.latest_step() == 1
    finally:
        release.set()


def test_wait_snapshot_fires_for_monolithic_and_sync_backends():
    """Degraded modes still terminate: monolithic snapshots signal at
    serialize end; sync backends are done before save() returns."""
    import tempfile
    state = _state(kb=64)
    for backend, chunk in (("fastpersist", 8), ("baseline", 0),
                           ("fastpersist-pipelined", 0)):
        with tempfile.TemporaryDirectory() as d:
            spec = CheckpointSpec(directory=d, backend=backend,
                                  fp=_cfg(snapshot_chunk_mb=chunk))
            with CheckpointEngine(spec) as eng:
                h = eng.save(state, 1)
                eng.wait_snapshot()     # must not hang
                h.result()
                assert h.snapshot_done()
                assert eng.latest_step() == 1


# ------------------------------------------------- whole-leaf pull
def _mixed_state(seed=0):
    """bf16, f32 and int32 leaves, the largest over several 1 MiB chunks."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    return {"params": jax.random.normal(k1, (640, 1024), jnp.bfloat16),
            "master": jax.random.normal(k2, (300, 1024), jnp.float32),
            "count": jax.random.randint(k3, (4099,), 0, 1 << 30, jnp.int32),
            "step": jnp.int32(7)}


def _stream_bytes(d, step):
    import glob
    import os
    files = sorted(glob.glob(os.path.join(d, f"ckpt_{step:08d}", "*.bin")))
    assert files
    return b"".join(open(f, "rb").read() for f in files)


@pytest.mark.parametrize("lookahead", [1 << 20, 256 << 20],
                         ids=["below-state", "above-state"])
def test_pull_writes_the_monolithic_bytes(monkeypatch, tmp_path,
                                          lookahead):
    """One state of bf16, f32 and int32 leaves saved chunked (the
    whole-leaf pull, its look-ahead below or above the state's 2.5 MiB)
    and monolithic: the same shard bytes on disk, and both load back
    equal."""
    monkeypatch.setattr(arena_mod, "LOOKAHEAD_BYTES", lookahead)
    state = _mixed_state()
    dirs = {}
    for mb in (1, 0):
        d = dirs[mb] = str(tmp_path / f"chunk{mb}")
        spec = CheckpointSpec(directory=d, backend="fastpersist-pipelined",
                              fp=_cfg(snapshot_chunk_mb=mb))
        with CheckpointEngine(spec) as eng:
            eng.save(state, 1, extras={"step": 1}).result()
            loaded, _ = eng.load(1, like=state)
            _assert_tree_equal(state, loaded)
    assert _stream_bytes(dirs[1], 1) == _stream_bytes(dirs[0], 1)


def _pull_events(monkeypatch, state, lookahead, chunk=64 << 10):
    """Fill an arena from ``state`` inline and return the fill's events
    in order: ("start", leaf), ("advance", watermark), ("release",)."""
    monkeypatch.setattr(arena_mod, "LOOKAHEAD_BYTES", lookahead)
    events = []
    real_own, real_advance = arena_mod._own, SnapshotProgress.advance
    leaves, treedef = jax.tree_util.tree_flatten_with_path(state)
    ids = {id(x): i for i, (_p, x) in enumerate(leaves)}

    def own(x):
        events.append(("start", ids[id(x)]))
        return real_own(x)

    def advance(self, watermark):
        events.append(("advance", watermark))
        return real_advance(self, watermark)

    monkeypatch.setattr(arena_mod, "_own", own)
    monkeypatch.setattr(SnapshotProgress, "advance", advance)
    arena = SerializeArena()
    man, bufs, progress, fill = arena.begin_snapshot(
        leaves, treedef, chunk_bytes=chunk,
        release=lambda: events.append(("release",)))
    fill()
    progress.wait_done()
    v = ByteStreamView(bufs)
    m2, b2 = SerializeArena().serialize(leaves, treedef)
    assert bytes(v.read(0, v.total)) == bytes(
        ByteStreamView(b2).read(0, m2.total_bytes))
    return events, man.records


def test_pull_runs_a_bounded_number_of_bytes_ahead(monkeypatch):
    """With a 1.5 MiB look-ahead over leaves of 0.25-1.25 MiB, a leaf's
    transfer starts only once the bytes started and not yet copied into
    the arena fit the look-ahead (or it is the leaf being copied), and
    each leaf starts once, in stream order."""
    k = jax.random.PRNGKey(3)
    state = {f"l{i}": jax.random.normal(k, (n * 256,), jnp.float32)
             for i, n in enumerate([1024, 256, 1280, 512, 768, 256])}
    events, records = _pull_events(monkeypatch, state, 1536 << 10)
    starts = [e[1] for e in events if e[0] == "start"]
    assert starts == list(range(len(records)))
    copied = 0          # records whose last byte reached the arena
    started = 0
    mark = 0
    for e in events:
        if e[0] == "advance":
            mark = e[1]
            while copied < len(records) and \
                    records[copied].offset + records[copied].nbytes <= mark:
                copied += 1
        elif e[0] == "start":
            started = e[1] + 1
            held = sum(r.nbytes for r in records[copied:started])
            assert held <= 1536 << 10 or e[1] == copied, (e, held)


@pytest.mark.parametrize("lookahead,copied_before", [
    (1536 << 10, True), (256 << 20, False)],
    ids=["below-state", "above-state"])
def test_pull_releases_once_the_last_leaf_landed(monkeypatch, lookahead,
                                                  copied_before):
    """``release`` comes once, right after the last leaf's transfer
    started and landed, and before the last record reaches the arena:
    below the state's size the earlier leaves were copied first, above
    it nothing was."""
    k = jax.random.PRNGKey(4)
    state = {f"l{i}": jax.random.normal(k, (n * 256,), jnp.float32)
             for i, n in enumerate([1024, 1024, 512, 768])}
    events, records = _pull_events(monkeypatch, state, lookahead)
    at = events.index(("release",))
    assert events.count(("release",)) == 1
    last_start = max(i for i, e in enumerate(events) if e[0] == "start")
    assert last_start < at
    advances = [e[1] for e in events[:at] if e[0] == "advance"]
    assert bool(advances) == copied_before
    assert max(advances, default=0) < records[-1].offset


def test_pull_releases_after_a_trailing_empty_leaf(monkeypatch):
    """A state whose last record is empty still releases, once."""
    state = {"a": jnp.ones((70000,), jnp.float32),
             "z": jnp.zeros((0,), jnp.float32)}
    events, _ = _pull_events(monkeypatch, state, 1 << 20)
    assert events.count(("release",)) == 1


def _gate_arena_copy(monkeypatch):
    """Hold the fill at its first piece copied into the arena (after the
    state's bytes reached the host) until the returned event is set."""
    release, started = threading.Event(), threading.Event()
    real_advance = SnapshotProgress.advance

    def gated(self, watermark):
        started.set()
        assert release.wait(30), "test fill never released"
        return real_advance(self, watermark)

    monkeypatch.setattr(SnapshotProgress, "advance", gated)
    return release, started


def test_donating_update_after_wait_snapshot_keeps_the_saved_state(
        monkeypatch, tmp_path):
    """A donating update of the state, dispatched once ``wait_snapshot``
    returned while the copy into the arena is held, leaves the committed
    checkpoint equal to the state as it was at the save; the handle's
    snapshot is done and the save is not."""
    state = _mixed_state()
    want = jax.tree.map(np.array, state)
    update = jax.jit(lambda s: jax.tree.map(lambda x: x + 1, s),
                     donate_argnums=0)
    release, started = _gate_arena_copy(monkeypatch)
    spec = CheckpointSpec(directory=str(tmp_path),
                          backend="fastpersist-pipelined",
                          fp=_cfg(snapshot_chunk_mb=1))
    try:
        with CheckpointEngine(spec) as eng:
            h = eng.save(state, 1, extras={"step": 1})
            eng.wait_snapshot()
            assert h.snapshot_done()
            new = jax.block_until_ready(update(state))
            assert started.wait(30) and not h.done()
            release.set()
            h.result()
            loaded, _ = eng.load(1, like=want)
            _assert_tree_equal(want, loaded)
            assert int(new["step"]) == int(want["step"]) + 1
    finally:
        release.set()


def test_host_leaf_changed_after_release_keeps_the_saved_state(
        monkeypatch, tmp_path):
    """A numpy leaf changed in place once ``wait_snapshot`` returned, with
    the copy into the arena still held, does not reach the checkpoint."""
    state = dict(_mixed_state(), host=np.arange(5000, dtype=np.float32))
    want = jax.tree.map(np.array, state)
    release, started = _gate_arena_copy(monkeypatch)
    spec = CheckpointSpec(directory=str(tmp_path),
                          backend="fastpersist-pipelined",
                          fp=_cfg(snapshot_chunk_mb=1))
    try:
        with CheckpointEngine(spec) as eng:
            h = eng.save(state, 1)
            eng.wait_snapshot()
            state["host"] += 1
            assert started.wait(30) and not h.done()
            release.set()
            h.result()
            loaded, _ = eng.load(1, like=want)
            _assert_tree_equal(want, loaded)
    finally:
        release.set()


_SHARDED = r"""
import os, sys, tempfile
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import arena
from repro.core.checkpointer import FastPersistConfig
from repro.core.engine import CheckpointEngine, CheckpointSpec
from repro.launch.mesh import make_mesh

mesh = make_mesh((4,), ('data',))
state = {
    'w': jax.device_put(jnp.arange(4096.0).reshape(64, 64),
                        NamedSharding(mesh, P('data'))),
    'r': jax.device_put(jnp.ones((8, 8), jnp.bfloat16),
                        NamedSharding(mesh, P())),
    'step': jnp.int32(3),                       # one device, uncommitted
}
for x in jax.tree.leaves(state):
    o = arena._own(x)
    assert o is not x and o.sharding == x.sharding, (o.sharding, x.sharding)
    for a, b in zip(x.addressable_shards, o.addressable_shards):
        assert a.device == b.device
        assert a.data.unsafe_buffer_pointer() == b.data.unsafe_buffer_pointer()
    np.testing.assert_array_equal(np.asarray(o), np.asarray(x))
with tempfile.TemporaryDirectory() as d:
    spec = CheckpointSpec(directory=d, backend='fastpersist-pipelined',
                          fp=FastPersistConfig(snapshot_chunk_mb=1))
    with CheckpointEngine(spec) as eng:
        eng.save(state, 1).result()
        got, _ = eng.load(1, like=state)
    for x, y in zip(jax.tree.leaves(state), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
print('ALL-OK')
"""


def test_sharded_pull_reads_each_shard_in_place():
    """Four host devices: the pull's own view of a sharded, a replicated
    and a single-device leaf keeps every shard on its device over the
    same buffer, and a save of the sharded state loads back equal."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    r = subprocess.run([sys.executable, "-c", _SHARDED], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "ALL-OK" in r.stdout


# ------------------------------------------------ device-side dirty masks
def _run_chain(d, device_dirty, states):
    spec = CheckpointSpec(
        directory=d, backend="fastpersist",
        fp=_cfg(keyframe_every=4, device_dirty=device_dirty,
                snapshot_chunk_mb=1))
    out = []
    with CheckpointEngine(spec) as eng:
        for i, s in enumerate(states):
            h = eng.save(s, i + 1, extras={"step": i + 1})
            out.append(h.result())
        # COPY each load: parallel loads return views into the engine's
        # read arena, which the next load refills (DESIGN.md §7)
        loads = [jax.tree.map(np.array, eng.load(i + 1, like=states[0])[0])
                 for i in range(len(states))]
    return out, loads


def test_device_dirty_delta_chain_bit_exact():
    """Delta chain driven by the Pallas change masks == the host-compare
    chain: same spans, bit-exact restores of every generation, and the
    device→host traffic of a delta save is ~the dirty bytes, not the
    stream."""
    states = [_state(kb=1024)]
    for i in range(3):
        states.append(_mutate(states[-1], frac=0.01, seed=10 + i))
    import tempfile
    with tempfile.TemporaryDirectory() as d1, \
            tempfile.TemporaryDirectory() as d2:
        dev_stats, dev_loads = _run_chain(d1, True, states)
        host_stats, host_loads = _run_chain(d2, False, states)
    for i, s in enumerate(states):
        _assert_tree_equal(s, dev_loads[i])
        _assert_tree_equal(s, host_loads[i])
    # keyframe then deltas, identical span structure on both paths
    assert dev_stats[0].delta is None and host_stats[0].delta is None
    for ds, hs in zip(dev_stats[1:], host_stats[1:]):
        assert ds.delta is not None and hs.delta is not None
        assert ds.delta["spans"] == hs.delta["spans"]
    # PCIe accounting: host compare re-reads the whole stream, device
    # masks move masks + dirty blocks only
    total = dev_stats[0].d2h_bytes
    assert total > 0
    for ds in dev_stats[1:]:
        assert 0 < ds.d2h_bytes < total // 10
    for hs in host_stats[1:]:
        assert hs.d2h_bytes == host_stats[0].d2h_bytes  # full stream


def test_device_dirty_survives_layout_change():
    """A shape change invalidates the device baseline: the next save
    falls back to a full keyframe instead of chaining off a stale
    image."""
    import tempfile
    s1 = _state(kb=256)
    s2 = _state(seed=5, kb=128)         # different shapes
    with tempfile.TemporaryDirectory() as d:
        spec = CheckpointSpec(
            directory=d, backend="fastpersist",
            fp=_cfg(keyframe_every=4, device_dirty=True,
                    snapshot_chunk_mb=1))
        with CheckpointEngine(spec) as eng:
            assert eng.save(s1, 1).result().delta is None
            assert eng.save(s2, 2).result().delta is None   # re-layout
            s3 = _mutate(s2, seed=7)
            st3 = eng.save(s3, 3).result()
            assert st3.delta is not None                    # chain resumes
            loaded, _ = eng.load(3, like=s3)
            _assert_tree_equal(s3, loaded)


# -------------------------------------------- PipelinedCheckpointer sync
class _SlowInner:
    """Inner checkpointer that signals on_snapshot mid-save and then
    blocks until released — the pipeline's wait_snapshot must return in
    between."""

    def __init__(self):
        self.on_snapshot = None
        self.release = threading.Event()
        self.saved = []

    def save(self, state, step, extras=None):
        if self.on_snapshot is not None:
            self.on_snapshot()
        assert self.release.wait(30)
        self.saved.append(step)
        return object()


def test_pipelined_wait_snapshot_overlaps_write():
    from repro.core.pipeline import PipelinedCheckpointer
    inner = _SlowInner()
    with PipelinedCheckpointer(inner) as p:
        try:
            p.submit({"x": 1}, 1)
            p.wait_snapshot()           # returns while save still blocked
            assert inner.saved == []
            assert p.stats.snapshot_stall_seconds >= 0.0
        finally:
            inner.release.set()
        p.wait()
        assert inner.saved == [1]


def test_pipelined_wait_snapshot_degrades_without_hook():
    """An inner without on_snapshot support: wait_snapshot degrades to
    the full-save wait (the finally-decrement), never hangs."""
    from repro.core.pipeline import PipelinedCheckpointer

    class Plain:
        __slots__ = ("saved",)          # no on_snapshot attribute

        def __init__(self):
            self.saved = []

        def save(self, state, step, extras=None):
            self.saved.append(step)
            return object()

    inner = Plain()
    with PipelinedCheckpointer(inner) as p:
        p.submit({"x": 1}, 1)
        p.wait_snapshot()
        assert inner.saved == [1]       # degraded == full wait
