"""Spans and counters of the save and restore paths.

    with obs.span("snapshot.xfer") as sp:
        host = np.asarray(piece)
    sp.seconds                      # set on exit, recording or not
    obs.count("snapshot.bytes", host.nbytes)

A span always times its interval, so the per-call ``*Stats`` fields can
be filled from it. Recording is on after :func:`enable`, and while a
``jax.profiler`` trace is being captured. While it is on, each span is
also a ``jax.profiler.TraceAnnotation`` named ``fp.<name>`` (with its
keyword arguments, such as ``step=``, as metadata), so it lands on the
host threads of the trace on the device's clock; and the span's count
and seconds, and each counter, add up in a table that :func:`totals`
returns. Recording off costs a flag check and two clock reads a span.

Names, by layer: ``train.*`` (the train loop), ``engine.*`` (the caller's
side of the engine), ``save.*`` (the save worker), ``snapshot.*`` (the
device-to-host copy), ``write.*`` (writer threads), ``restore*`` and
``load.*`` (a resume), ``read.*`` (reader threads).
"""
from __future__ import annotations

import threading
import time

_enabled = False
_lock = threading.Lock()
_spans: dict = {}           # name -> [n, seconds]
_counters: dict = {}        # name -> int
_annotation = None          # jax.profiler.TraceAnnotation, on first use


def _trace_annotation():
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation


def recording() -> bool:
    """True after :func:`enable`, or while a profiler trace is captured."""
    return _enabled or _trace_annotation().is_enabled()


class span:
    """Context manager over one interval; ``.seconds`` holds its length
    after exit and ``.t0`` its ``time.perf_counter()`` start."""

    __slots__ = ("name", "args", "t0", "seconds", "_ann")

    def __init__(self, name: str, **args):
        self.name = name
        self.args = args
        self.t0 = 0.0
        self.seconds = 0.0
        self._ann = None

    def __enter__(self):
        if recording():
            self._ann = _trace_annotation()("fp." + self.name, **self.args)
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self.t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
            with _lock:
                rec = _spans.setdefault(self.name, [0, 0.0])
                rec[0] += 1
                rec[1] += self.seconds
        return False


def count(name: str, n: int):
    """Add ``n`` to the counter ``name`` while recording."""
    if recording():
        with _lock:
            _counters[name] = _counters.get(name, 0) + int(n)


def enable(on: bool = True):
    global _enabled
    _enabled = bool(on)


def reset():
    with _lock:
        _spans.clear()
        _counters.clear()


def totals() -> dict:
    """``{"spans": {name: {"n", "s"}}, "counters": {name: int}}`` of
    everything recorded since the last :func:`reset`."""
    with _lock:
        return {"spans": {k: {"n": n, "s": s}
                          for k, (n, s) in _spans.items()},
                "counters": dict(_counters)}
