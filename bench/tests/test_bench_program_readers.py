"""The readers of the program's own spans and counters: their arithmetic
on hand-made totals, silence where a span or counter is missing, and the
recorder's table read where the run kept no totals."""
import importlib
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

GB = 1e9
SPANS = {"spans": {"snapshot.xfer": {"n": 460, "s": 1.5},
                   "snapshot.copy": {"n": 460, "s": 0.25},
                   "read.crc": {"n": 800, "s": 4.0},
                   "restore.h2d": {"n": 8, "s": 2.4}},
         "counters": {"snapshot.bytes": 3 * GB, "read.bytes": 24 * GB,
                      "restore.bytes": 24 * GB}}
READERS = [("snapshot_xfer_gbps", "snapshot.xfer", "snapshot.bytes", 2.0),
           ("snapshot_copy_gbps", "snapshot.copy", "snapshot.bytes", 12.0),
           ("read_crc_gbps", "read.crc", "read.bytes", 6.0),
           ("h2d_gbps", "restore.h2d", "restore.bytes", 10.0)]


def read(name, records):
    mod = importlib.import_module("metrics." + name)
    return mod.read({"records": records, "trace": None, "peaks": {}})


@pytest.mark.parametrize("name,span,counter,want", READERS,
                         ids=[r[0] for r in READERS])
def test_reader_arithmetic(name, span, counter, want):
    assert read(name, {"spans": SPANS}) == pytest.approx(want)


@pytest.mark.parametrize("name,span,counter,want", READERS,
                         ids=[r[0] for r in READERS])
def test_reader_finds_nothing(name, span, counter, want):
    no_span = {"spans": {k: v for k, v in SPANS["spans"].items()
                         if k != span},
               "counters": SPANS["counters"]}
    no_counter = {"spans": SPANS["spans"],
                  "counters": {k: v for k, v in SPANS["counters"].items()
                               if k != counter}}
    assert read(name, {"spans": no_span}) is None
    assert read(name, {"spans": no_counter}) is None
    assert read(name, {"spans": {"spans": {}, "counters": {}}}) is None


def test_reader_takes_the_recorders_table():
    from repro import obs
    obs.reset()
    obs.enable()
    try:
        with obs.span("restore.h2d"):
            pass
        obs.count("restore.bytes", 1000)
        want = 1000 / obs.totals()["spans"]["restore.h2d"]["s"] / GB
        assert read("h2d_gbps", {}) == pytest.approx(want)
        assert read("read_crc_gbps", {}) is None
    finally:
        obs.enable(False)
        obs.reset()
