"""trace_reduce against hand-computed numbers: a made-up trace whose
answer is worked out below, and a small trace recorded on a TPU v5e
(``data/small.xplane.pb``, made by ``make_trace_data.py``)."""
import os
import sys
from types import SimpleNamespace as NS

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import trace_reduce  # noqa: E402

DATA = os.path.join(BENCH, "tests", "data", "small.xplane.pb")


def ev(name, start, end):
    return NS(name=name, start_ns=start, duration_ns=end - start)


def test_made_up_trace():
    ops = [ev("fusion", 0, 10), ev("fusion", 5, 15), ev("copy", 20, 30),
           ev("fusion", 60, 100)]
    planes = [
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Ops", events=ops),
            NS(name="Steps", events=[ev("step", 0, 100)])]),
        NS(name="/host:CPU", lines=[NS(name="python", events=[
            ev("bench.train_step", 0, 16), ev("bench.sleep", 14, 21),
            ev("bench.wait_snapshot", 25, 58), ev("other", 30, 60)])]),
    ]
    out = trace_reduce.reduce_planes(planes)
    # busy: [0,15] + [20,30] + [60,100] = 15 + 10 + 40 ns
    assert out["busy_s"] == pytest.approx(65e-9)
    assert out["device_span_s"] == pytest.approx(100e-9)
    # fusion 10 + 10 + 40 = 60 ns, copy 10 ns
    assert out["breakdown"]["device_ops"] == [["fusion", 60e-9],
                                              ["copy", 10e-9]]
    # gaps (30,60) under wait_snapshot 28 of 30 ns; (15,20) under sleep
    assert out["breakdown"]["idle_gaps"] == [["wait_snapshot", 30e-9],
                                             ["sleep", 5e-9]]


def test_no_device_op_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.reduce_planes([NS(name="/host:CPU", lines=[])])


def test_recorded_trace():
    """Busy by a sweep over every op's start and end (not the merge the
    reducer uses), the top op by summed time, and three gaps each
    mostly under the 20 ms host sleep."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(DATA)
    planes = list(data.planes)
    tpu = [p for p in planes if p.name.startswith("/device:TPU:")]
    ops = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
           for p in tpu for ln in p.lines if ln.name == "XLA Ops"
           for e in ln.events]
    points = sorted([(s, 1) for _, s, _ in ops] + [(e, -1) for _, _, e in ops])
    busy, depth, since = 0, 0, None
    for t, d in points:
        if depth == 0 and d == 1:
            since = t
        depth += d
        if depth == 0:
            busy += t - since
    per_op = {}
    for n, s, e in ops:
        per_op[n] = per_op.get(n, 0) + (e - s)
    top = max(per_op, key=per_op.get)

    out = trace_reduce.reduce_planes(planes)
    assert out["busy_s"] == pytest.approx(busy / 1e9 / len(tpu))
    assert out["breakdown"]["device_ops"][0][0] == \
        top[:trace_reduce.NAME_CHARS]
    gaps = out["breakdown"]["idle_gaps"]
    assert [g[0] for g in gaps[:2]] == ["sleep", "sleep"]
    assert all(0.015 < g[1] < 0.2 for g in gaps[:2])
