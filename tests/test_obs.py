"""Spans and counters of the save and restore paths (``repro.obs``):
what recording off leaves, and with recording on the counts of each span
and the identities between counters, spans and the per-call Stats. No
timing is asserted."""
import dataclasses
import time

import jax
import pytest

from repro import obs
from repro.configs import get_config, reduced
from repro.core.checkpointer import FastPersistConfig
from repro.core.engine import FastPersistBackend
from repro.train.trainer import CheckpointPolicy, Trainer, TrainerConfig

MiB = 1 << 20
#: a tiny model whose embedding leaves outgrow one 1 MiB snapshot chunk
#: (2560 x 256: 2.5 MiB in f32, 1.25 MiB in bf16)
MODEL = dataclasses.replace(reduced(get_config("stablelm_1_6b")),
                            vocab_size=2560)

SPANS = ["train.data", "train.dispatch", "train.save", "engine.save",
         "engine.throttle", "engine.wait_snapshot", "engine.wait",
         "engine.drain", "save.persist", "save.commit", "snapshot.fill",
         "snapshot.ready", "snapshot.xfer", "snapshot.copy", "write.stream",
         "restore", "restore.find", "restore.load", "restore.h2d",
         "load.alloc", "load.read", "load.verify", "load.materialize",
         "read.submit", "read.wait", "read.crc"]
COUNTERS = ["snapshot.bytes", "restore.bytes", "read.bytes"]


@pytest.fixture
def recording():
    obs.reset()
    obs.enable()
    try:
        yield
    finally:
        obs.enable(False)
        obs.reset()


def _trainer(directory, steps, every=2):
    return Trainer(TrainerConfig(
        model=MODEL, steps=steps, global_batch=2, seq_len=16,
        log_every=10 ** 9,
        checkpoint=CheckpointPolicy(
            directory=str(directory), every=every,
            backend="fastpersist-pipelined",
            fp=FastPersistConfig(snapshot_chunk_mb=1))))


def _pieces(state, chunk=MiB):
    """(pieces, partial pieces) of one chunked snapshot of ``state``:
    a leaf that fits one chunk is one whole piece."""
    sizes = [x.size * x.dtype.itemsize for x in jax.tree.leaves(state)]
    whole = sum(1 for n in sizes if 0 < n <= chunk)
    partial = sum(-(-n // chunk) for n in sizes if n > chunk)
    return whole + partial, partial


def _saves(tr):
    return [r.save.result() for r in tr.history if r.save is not None]


def test_recording_off_leaves_nothing_and_fills_stats(tmp_path):
    obs.reset()
    assert not obs.recording()
    tr = _trainer(tmp_path, 4)
    tr.run()
    assert obs.totals() == {"spans": {}, "counters": {}}
    stats = _saves(tr)
    assert len(stats) == 2
    assert all(st.snapshot_seconds > 0 and st.commit_seconds > 0
               for st in stats)
    assert all(w.seconds > 0 for st in stats for w in st.per_writer)


def test_span_off_still_times():
    with obs.span("x", step=3) as sp:
        time.sleep(0)
    assert sp.seconds >= 0 and sp.t0 > 0
    assert obs.totals()["spans"] == {}


def test_save_spans_and_counters(tmp_path, recording):
    """A chunked save waits once for the state, moves each leaf whole
    (one transfer a leaf) and copies it into the arena piece by piece."""
    tr = _trainer(tmp_path, 4)
    tr.run()
    t = obs.totals()
    sp, ct = t["spans"], t["counters"]
    stats = _saves(tr)
    n_saves = len(stats)
    assert n_saves == 2
    pieces, partial = _pieces(tr.state)
    assert partial > 0
    assert sp["snapshot.fill"]["n"] == n_saves
    assert sp["snapshot.copy"]["n"] == n_saves * pieces
    assert sp["snapshot.ready"]["n"] == n_saves
    assert sp["snapshot.xfer"]["n"] == \
        n_saves * len(jax.tree.leaves(tr.state))
    assert ct["snapshot.bytes"] == sum(st.d2h_bytes for st in stats)
    assert ct["snapshot.bytes"] == n_saves * stats[0].total_bytes
    # the Stats fields are the spans' own readings, not a second clock
    assert sp["snapshot.fill"]["s"] == sum(st.snapshot_seconds
                                           for st in stats)
    assert sp["save.commit"]["s"] == sum(st.commit_seconds for st in stats)
    assert sp["write.stream"]["n"] == sum(st.n_writers for st in stats)
    assert sp["write.stream"]["s"] == pytest.approx(
        sum(w.seconds for st in stats for w in st.per_writer), rel=1e-9)
    for name in ("train.save", "engine.save", "engine.throttle",
                 "save.persist", "save.commit"):
        assert sp[name]["n"] == n_saves, name
    assert sp["train.data"]["n"] == sp["train.dispatch"]["n"] == 4
    assert sp["engine.wait_snapshot"]["n"] == 4


def test_one_save_fill_equals_its_snapshot_seconds(tmp_path, recording):
    tr = _trainer(tmp_path, 2)
    tr.run()
    (st,) = _saves(tr)
    assert obs.totals()["spans"]["snapshot.fill"]["s"] == \
        st.snapshot_seconds


def test_restore_spans_and_counters(tmp_path, recording):
    tr = _trainer(tmp_path, 2)
    tr.run()
    stream = _saves(tr)[0].total_bytes
    obs.reset()
    fresh = _trainer(tmp_path, 2)
    assert fresh.restore() == 2
    t = obs.totals()
    assert t["counters"]["read.bytes"] == stream
    assert t["counters"]["restore.bytes"] == stream
    for name in ("restore", "restore.find", "restore.load", "restore.h2d",
                 "load.alloc", "load.read", "load.verify",
                 "load.materialize"):
        assert t["spans"][name]["n"] == 1, name
    reads = t["spans"]["read.submit"]["n"]
    assert reads >= 1
    assert t["spans"]["read.wait"]["n"] == t["spans"]["read.crc"]["n"] \
        == reads


def test_every_name_appears(tmp_path, recording):
    tr = _trainer(tmp_path, 4)
    tr.run()
    _trainer(tmp_path, 4).restore()
    t = obs.totals()
    assert sorted(set(SPANS) - set(t["spans"])) == []
    assert sorted(set(COUNTERS) - set(t["counters"])) == []


def test_ckpt_stall_includes_the_throttle(tmp_path, recording,
                                          monkeypatch):
    """A save every step behind a slow persist: each save waits in the
    throttle for the previous one to commit, and the steps' stall holds
    that wait."""
    persist = FastPersistBackend.write_payload_sharded

    def slow(self, *a, **kw):
        time.sleep(0.05)
        return persist(self, *a, **kw)
    monkeypatch.setattr(FastPersistBackend, "write_payload_sharded", slow)
    tr = _trainer(tmp_path, 4, every=1)
    tr.run()
    throttle = obs.totals()["spans"]["engine.throttle"]
    assert throttle["n"] == 4
    # the steps' records leave out the drain that ends the run
    assert sum(r.ckpt_stall for r in tr.history) >= throttle["s"]
