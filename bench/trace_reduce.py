"""From a profiler trace to device busy time and the breakdown.

Busy is the union of the intervals in which an operation ran on a
device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane),
averaged over the devices. An idle gap is a stretch between busy
intervals; each is named by the benchmark's host span
(``bench.<layer>``) that covers most of it, or ``host`` when none does.
Only ``jax.profiler.ProfileData`` is used.
"""
from __future__ import annotations

import glob
import os

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
TOP = 10
#: an op's name is its HLO text; its head (name, result shape) will do
NAME_CHARS = 100


def _merge(iv):
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap(a0, a1, b0, b1):
    return max(0, min(a1, b1) - max(a0, b0))


def reduce_planes(planes) -> dict:
    """``planes``: ProfileData planes (or objects shaped alike: ``name``,
    ``lines`` with ``name`` and ``events`` of ``name``, ``start_ns``,
    ``duration_ns``). Returns busy seconds per device (mean), the span
    the device planes cover, and the breakdown."""
    devices, spans = [], []
    for p in planes:
        if p.name.startswith("/device:TPU:"):
            ops = [ln for ln in p.lines if ln.name == OPS_LINE]
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for ln in ops for e in ln.events]
            if evs:
                devices.append(evs)
        elif p.name.startswith("/host:"):
            for ln in p.lines:
                for e in ln.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name[len(SPAN_PREFIX):], e.start_ns,
                                      e.start_ns + e.duration_ns))
    if not devices:
        raise ValueError("the trace holds no device operation")
    busy, op_time, gaps = [], {}, []
    for evs in devices:
        merged = _merge([(s, e) for _, s, e in evs])
        busy.append(sum(e - s for s, e in merged))
        for name, s, e in evs:
            name = name[:NAME_CHARS]
            op_time[name] = op_time.get(name, 0) + (e - s)
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            gaps.append((e0, s1))
    lo = min(s for evs in devices for _, s, _ in evs)
    hi = max(e for evs in devices for _, _, e in evs)
    named = []
    for g0, g1 in gaps:
        best, cover = "host", 0
        for name, s, e in spans:
            ov = _overlap(g0, g1, s, e)
            if ov > cover:
                best, cover = name, ov
        named.append([best, (g1 - g0) / 1e9])
    named.sort(key=lambda x: -x[1])
    ops = sorted(op_time.items(), key=lambda x: -x[1])[:TOP]
    return {"busy_s": sum(busy) / len(busy) / 1e9,
            "device_span_s": (hi - lo) / 1e9,
            "breakdown": {
                "device_ops": [[n, t / 1e9 / len(devices)] for n, t in ops],
                "idle_gaps": named[:TOP]}}


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise ValueError(f"{trace_dir}: expected one trace, found "
                         f"{len(found)}")
    return found[0]


def reduce_dir(trace_dir: str, window_s: float) -> dict:
    from jax.profiler import ProfileData
    # the planes are views into the ProfileData: keep it alive
    data = ProfileData.from_file(find_xplane(trace_dir))
    out = reduce_planes(list(data.planes))
    out["window_s"] = window_s
    return out
