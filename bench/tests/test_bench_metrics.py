"""Each per-layer reader's arithmetic on recorded save and write
statistics, and silence where it finds nothing to read."""
import importlib
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import peaks  # noqa: E402

GB = 1e9
#: two saves as SaveStats would record them
SAVES = [{"d2h_bytes": 2 * GB, "snapshot_seconds": 4.0},
         {"d2h_bytes": 2 * GB, "snapshot_seconds": 6.0}]
RESUMES = {"resumes": 4, "state_bytes": 3 * GB, "load_seconds": 8.0,
           "restore_seconds": 10.0, "first_step_seconds": 2.0}


def read(name, ctx):
    return importlib.import_module("metrics." + name).read(ctx)


def ctx(records, trace=None):
    return {"records": records, "trace": trace,
            "peaks": peaks.for_device("TPU v5 lite")}


@pytest.mark.parametrize("name,records,trace,want", [
    ("d2h_gbps", {"saves": SAVES}, None, 4.0 / 10.0),
    ("device_idle_pct", {}, {"busy_s": 15.0, "window_s": 20.0}, 25.0),
    ("load_gbps", RESUMES, None, 12.0 / 8.0),
    ("h2d_s", RESUMES, None, (10.0 - 8.0 + 2.0) / 4),
])
def test_reader_arithmetic(name, records, trace, want):
    assert read(name, ctx(records, trace)) == pytest.approx(want)


@pytest.mark.parametrize("name", ["d2h_gbps", "load_gbps", "h2d_s",
                                  "device_idle_pct"])
def test_reader_finds_nothing(name):
    assert read(name, ctx({})) is None


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.for_device("TPU v9 imaginary")
