"""The one generator of the benchmark's traffic. A traffic file in
``traffic/`` names its ``kind`` (the job event it drives) and that
event's parameters; this module turns the two into a run:

- ``steady``: training with an async pipelined save every ``save_every``
  steps. The window holds whole save intervals and closes at an
  interval boundary once ``--seconds`` have passed (or after
  ``max_intervals``, which bounds what a run writes however fast steps
  become), with the last save's snapshot landed.
- ``resume``: one checkpoint saved in set-up, then cold resumes, each a
  fresh Trainer and engine: drop the files from the page cache,
  ``Trainer.restore``, and the first train step's loss on the host.

Each job returns a :class:`Run`: what the window measured, the records
the per-layer readers take, and the readings the verdict compares.
"""
from __future__ import annotations

import gc
import time
from dataclasses import dataclass, field

import harness as hz


@dataclass
class Run:
    setup_s: float = 0.0
    window_s: float = 0.0
    attempted: int = 0
    failed: int = 0
    metrics: dict = field(default_factory=dict)     # end-to-end
    records: dict = field(default_factory=dict)     # per-layer inputs
    readings: dict = field(default_factory=dict)    # compared numbers
    device: dict = field(default_factory=dict)
    bytes_written: int = 0     # checkpoint bytes the engine wrote


class Window:
    """Marks the measured window; under ``--trace 1`` the profiler
    records exactly it."""

    def __init__(self, trace_dir=None):
        self.trace_dir = trace_dir
        self.t0 = self.t1 = None

    def __enter__(self):
        import jax
        self.compiles = hz.COMPILES.snapshot() if hz.COMPILES else None
        if self.trace_dir:
            jax.profiler.start_trace(self.trace_dir)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        import jax
        self.t1 = time.perf_counter()
        if self.trace_dir:
            jax.profiler.stop_trace()
        if self.compiles is not None:
            hz.log(f"window: {self.seconds:.3f}s; "
                   f"{hz.COMPILES.since(self.compiles)}")

    @property
    def seconds(self):
        return self.t1 - self.t0


def _stream_bytes(state) -> int:
    import jax
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(state))


def _tokens(traffic) -> int:
    return traffic["batch"] * traffic["seq_len"]


def _start(conf, traffic, seed, every):
    """A trainer saving every ``every`` steps, holding the state made
    from the seed; with the run's (weights, data) seeds."""
    wseed, dseed = hz.run_seeds(seed)
    tr = hz.trainer(conf, traffic, dseed, hz.CKPT_DIR, every)
    tr.state = hz.make_state(conf, wseed)
    hz.check_structure(tr.state, tr.model)
    tr.engine.invalidate_arena()
    return tr, wseed, dseed


def _phase(t_start, what):
    """One line on standard error: the set-up phase that just ended, the
    seconds since the process started, and the compiles so far."""
    c = f"; {hz.COMPILES.since((0, 0, 0, 0.0))}" if hz.COMPILES else ""
    hz.log(f"set-up: {what} at {time.perf_counter() - t_start:.3f}s{c}")


def _close(tr):
    """Stop the trainer's engine and free its state on the device."""
    tr.engine.close()
    tr.state = None
    gc.collect()


def _reference_gaps(conf, traffic, wseed, dseed, prog) -> dict:
    """The reference's first steps, compared with the program's."""
    from reference import train_readings
    ref = train_readings(conf["model"], conf["optimizer"], wseed, dseed,
                         traffic["batch"], traffic["seq_len"],
                         traffic["check_steps"])
    return hz.train_gaps(prog, ref, conf["skip_leaf_frac"])


def steady(cell, seed, seconds, trace_dir, devs, t_start) -> Run:
    conf, traffic = cell["config"], cell["traffic"]
    m, o = conf["model"], conf["optimizer"]
    N, c = traffic["save_every"], traffic["check_steps"]
    run = Run()

    tr, wseed, dseed = _start(conf, traffic, seed, N)
    _phase(t_start, "state built")
    spans = hz.Spans()
    spans.watch_saves(tr.engine)
    spans.wrap(tr.engine, "wait_snapshot", "wait_snapshot")
    spans.wrap(tr, "train_step", "train_step")
    # the job's step counter starts at N - c, so that the c checked
    # steps end on the first save boundary: that save warms the save
    # path (arena, snapshot programs) and drains before the window
    prog = hz.program_readings(tr, m, o, wseed, c, N - c)
    _phase(t_start, "checked steps done, warm-up save called")
    spans.join()
    run.setup_s = time.perf_counter() - t_start
    w = spans.saves[0]
    st = w["handle"].result()
    hz.log(f"set-up: {run.setup_s:.3f}s; warm-up save durable after "
           f"{w['t_done'] - w['t_call']:.4f}s (snapshot "
           f"{st.snapshot_seconds:.4f}s, persist {st.seconds:.4f}s, "
           f"commit {st.commit_seconds:.4f}s)")
    spans.reset()
    first_save = len(spans.saves)

    # the window: whole intervals of N steps, each ending in a save that
    # overlaps the next interval; between intervals nothing drains
    drain = tr.engine.drain
    tr.engine.drain = lambda: None
    k = 1
    with Window(trace_dir) as win:
        while True:
            tr.cfg.steps = (k + 1) * N
            tr.run(start_step=k * N)
            k += 1
            if time.perf_counter() - win.t0 >= seconds \
                    or k - 1 >= traffic["max_intervals"]:
                break
        tr.engine.wait_snapshot()
    tr.engine.drain = drain
    blocked = hz.blocked_seconds(spans)
    drain()
    spans.join()
    saves = spans.saves[first_save:]
    n_int = k - 1
    run.window_s = win.seconds
    run.attempted = len(saves)
    run.device = hz.device_info(devs)

    stats = [s["handle"].result() for s in saves]
    run.metrics = {
        "train_tokens_per_s": n_int * N * _tokens(traffic) / win.seconds,
        "stall_per_save_s": blocked / len(saves),
    }
    for s, st in zip(saves, stats):
        hz.log(f"save of step {s['step']}: durable after "
               f"{s['t_done'] - s['t_call']:.4f}s, snapshot "
               f"{st.snapshot_seconds:.4f}s, persist {st.seconds:.4f}s, "
               f"commit {st.commit_seconds:.4f}s")
    run.records = {
        "saves": [{"d2h_bytes": st.d2h_bytes,
                   "snapshot_seconds": st.snapshot_seconds}
                  for st in stats],
    }

    # the newest committed step, read back through the system's load
    # path, against the live state it was saved from
    last = saves[-1]["step"]
    got, manifest = tr.engine.load(last, like=tr.state, parallel="auto")
    run.readings["ckpt_bytes_differing"] = hz.differing_bytes(got, tr.state)
    run.readings["ckpt_step_mismatch"] = int(
        manifest.extras.get("step") != last
        or tr.engine.latest_step() != last)
    del got
    run.bytes_written = tr.engine.stats.bytes_written
    _close(tr)
    del tr
    hz.clean_ckpt()

    run.readings.update(_reference_gaps(conf, traffic, wseed, dseed, prog))
    run.failed = sum(s["handle"].exception() is not None for s in saves)
    return run


def resume(cell, seed, seconds, trace_dir, devs, t_start) -> Run:
    import jax
    import numpy as np

    conf, traffic = cell["config"], cell["traffic"]
    m, o = conf["model"], conf["optimizer"]
    c = traffic["check_steps"]
    run = Run()

    # set-up: c steps checked against the reference, saved at step c,
    # then the uninterrupted step c + 1, kept on the host
    tr, wseed, dseed = _start(conf, traffic, seed, c)
    _phase(t_start, "state built")
    prog = hz.program_readings(tr, m, o, wseed, c, 0)
    st = tr.history[c - 1].save.result()      # run() drained it
    _phase(t_start, f"checked steps and save durable (snapshot "
           f"{st.snapshot_seconds:.4f}s, persist {st.seconds:.4f}s, "
           f"commit {st.commit_seconds:.4f}s)")
    saved_bytes = _stream_bytes(tr.state)
    tr.cfg.checkpoint.every = 10 ** 9
    tr.cfg.steps = c + 1
    tr.run(start_step=c)
    want_loss = float(tr.history[-1].loss)
    want = jax.tree.map(np.asarray, tr.state)
    _phase(t_start, "uninterrupted step, its state on the host")
    step_fn = tr.train_step
    run.bytes_written = tr.engine.stats.bytes_written
    _close(tr)
    del tr

    spans = hz.Spans()

    def one_resume():
        """A fresh Trainer and engine (the compiled step is kept, as a
        job's own process keeps it) resumes and takes one step."""
        hz.evict(hz.CKPT_DIR)
        t = hz.trainer(conf, traffic, dseed, hz.CKPT_DIR, 10 ** 9)
        t.train_step = step_fn
        spans.wrap(t.engine, "load", "engine.load")
        spans.wrap(t, "train_step", "train_step")
        t0 = time.perf_counter()
        with spans.span("restore"):
            start = t.restore()
        with spans.span("first_step"):
            t.cfg.steps = start + 1
            t.run(start_step=start)
            loss = float(t.history[-1].loss)
        return t, time.perf_counter() - t0, start, loss

    _close(one_resume()[0])            # warm-up: the same shapes
    run.setup_s = time.perf_counter() - t_start
    _phase(t_start, f"warm-up resume done (engine.load "
           f"{spans.t.get('engine.load', 0.0):.4f}s)")
    spans.reset()

    times, bad = [], 0
    with Window(trace_dir) as win:
        while True:
            t, dt, start, loss = one_resume()
            times.append(dt)
            bad += int(start != c or loss != want_loss)
            if time.perf_counter() - win.t0 >= seconds:
                break
            _close(t)
    run.window_s = win.seconds
    run.attempted = len(times)
    run.failed = bad
    run.device = hz.device_info(devs)
    run.metrics = {"resume_s": sum(times) / len(times)}
    load_s = spans.t.get("engine.load", 0.0)
    run.records = {
        "resumes": len(times), "state_bytes": saved_bytes,
        "load_seconds": load_s,
        "restore_seconds": spans.t.get("restore", 0.0),
        "first_step_seconds": spans.t.get("first_step", 0.0),
    }
    # the last resume's state after its step against the uninterrupted
    # run's, bit for bit; every resumed loss against the uninterrupted
    run.readings["resume_bytes_differing"] = hz.differing_bytes(t.state,
                                                                want)
    run.readings["resume_loss_mismatches"] = bad
    _close(t)
    del t, want
    hz.clean_ckpt()

    run.readings.update(_reference_gaps(conf, traffic, wseed, dseed, prog))
    return run


JOBS = {"steady": steady, "resume": resume}
