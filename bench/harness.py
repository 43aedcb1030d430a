"""Pieces every job of the benchmark shares: the device check, the cell
lookup, the state made from the seed, the spans and clocks wrapped
round the engine, and the comparison with the plain reference.

Nothing here imports the system under test at module level: the
harness must fail cleanly in a checkout that holds only the benchmark.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import shutil
import sys
import threading
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CKPT_DIR = os.path.join(BENCH, ".ckpt")
TRACE_DIR = os.path.join(BENCH, ".trace")
SEED_MOD = 2 ** 31 - 1
#: the run's CompileLog, once the devices are found
COMPILES = None


class NoDevice(Exception):
    """No accelerator, or fewer chips than the cell asks for."""


def log(*a):
    print(*a, file=sys.stderr, flush=True)


# ---------------------------------------------------------------- cells
def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, bench_json: str = None) -> dict:
    """The workload entry of ``name`` with its configuration and traffic
    files loaded: {"cell", "config", "traffic"}."""
    spec = load_json(bench_json or os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    return {"cell": cell, "spec": spec,
            "config": load_json(os.path.join(ROOT, conf["file"])),
            "traffic": load_json(os.path.join(BENCH, "traffic",
                                              cell["traffic"] + ".json"))}


def run_seeds(seed: int):
    """(weights seed, data seed) of a run: any whole number maps into
    the 31 bits a PRNG key and the token stream take."""
    s = int(seed) % SEED_MOD
    return s, (s * 7919 + 17) % SEED_MOD


# --------------------------------------------------------------- device
def require_tpu(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"needs a TPU, JAX found {devs[0].platform!r} "
                       f"({len(devs)} device(s))")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} TPU chips, JAX found "
                       f"{len(devs)}")
    return devs[:chips]


def device_info(devs) -> dict:
    peak = 0
    for d in devs:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


# ----------------------------------------------------------- the model
def model_config(config: dict):
    """The system's ModelConfig of this configuration file: the named
    model with every size the file states."""
    from repro.configs import get_config
    from repro.configs.base import MoEConfig
    m = config["model"]
    base = get_config(config["model_id"])
    changes = dict(n_layers=m["n_layers"], d_model=m["d_model"],
                   n_heads=m["n_heads"], n_kv_heads=m["n_heads"],
                   d_ff=m["d_ff"], vocab_size=m["vocab_size"],
                   rope_theta=m["rope_theta"], norm_eps=m["norm_eps"])
    if m.get("n_experts"):
        changes["moe"] = MoEConfig(
            n_experts=m["n_experts"], top_k=m["top_k"],
            aux_loss_coef=m["aux_loss_coef"],
            capacity_factor=m["capacity_factor"])
    return dataclasses.replace(base, **changes)


def make_state(config: dict, wseed: int):
    """The train state from the seed, built on the device in one jitted
    call: bf16 params, fp32 master (the bf16 values), zero moments."""
    import jax
    import jax.numpy as jnp
    from repro.optim.adam import AdamState
    from repro.train.steps import TrainState

    from reference import init_params

    def build():
        p32 = init_params(config["model"], wseed)
        bf = jax.tree.map(lambda p: p.astype(jnp.bfloat16), p32)
        master = jax.tree.map(lambda p: p.astype(jnp.float32), bf)
        zeros = jax.tree.map(jnp.zeros_like, master)
        return TrainState(bf, AdamState(jnp.zeros((), jnp.int32), master,
                                        zeros, jax.tree.map(jnp.zeros_like,
                                                            master)))
    return jax.jit(build)()


def check_structure(state, model):
    """The benchmark's tree must be the tree the system builds."""
    import jax
    from repro.train.steps import init_train_state
    want = jax.eval_shape(lambda k: init_train_state(model, k),
                          jax.random.PRNGKey(0))
    got = jax.tree.map(lambda x: (x.shape, x.dtype), state)
    exp = jax.tree.map(lambda x: (x.shape, x.dtype), want)
    if jax.tree.structure(got) != jax.tree.structure(exp) or \
            jax.tree.leaves(got) != jax.tree.leaves(exp):
        raise SystemExit("the benchmark's state tree differs from the "
                         "system's: fix the configuration file")


def trainer(config: dict, traffic: dict, data_seed: int, directory,
            every: int):
    """A Trainer at the engine's default checkpoint policy (the
    pipelined FastPersist backend, arena, chunked snapshots, O_DIRECT,
    fsync'd commit, one save in flight, nothing deleted)."""
    from repro.core.checkpointer import FastPersistConfig
    from repro.optim.adam import AdamConfig
    from repro.train.trainer import CheckpointPolicy, Trainer, TrainerConfig
    pol = None
    if directory is not None:
        pol = CheckpointPolicy(directory=directory, every=every,
                               backend=config["checkpoint"]["backend"],
                               fp=FastPersistConfig())
    return Trainer(TrainerConfig(
        model=model_config(config), steps=0,
        global_batch=traffic["batch"], seq_len=traffic["seq_len"],
        seed=data_seed, opt=AdamConfig(**config["optimizer"]),
        checkpoint=pol, log_every=10 ** 9))


# ------------------------------------------------------- spans, clocks
class Spans:
    """Host clocks round the calls into each layer. Every span is also a
    ``jax.profiler.TraceAnnotation`` named ``bench.<layer>``, so the
    idle gaps of a trace carry them. Wraps the instance's methods only;
    the classes are untouched."""

    def __init__(self):
        self.t = {}            # name -> summed seconds
        self.saves = []        # [{"step", "t_call", "t_done", "handle"}]
        self._watchers = []
        self._lock = threading.Lock()

    @contextlib.contextmanager
    def span(self, name):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench." + name):
            try:
                yield
            finally:
                dt = time.perf_counter() - t0
                with self._lock:
                    self.t[name] = self.t.get(name, 0.0) + dt

    def wrap(self, obj, attr, name):
        fn = getattr(obj, attr)

        def wrapped(*a, **kw):
            with self.span(name):
                return fn(*a, **kw)
        setattr(obj, attr, wrapped)

    def watch_saves(self, engine):
        """Time each save from its call to its COMMIT (a waiter thread
        per save), besides the span round the call itself."""
        save = engine.save

        def wrapped(state, step, extras=None):
            t0 = time.perf_counter()
            with self.span("engine.save"):
                h = save(state, step, extras)
            rec = {"step": step, "t_call": t0, "t_done": None, "handle": h}

            def wait():
                try:
                    h.wait()
                finally:
                    rec["t_done"] = time.perf_counter()
            th = threading.Thread(target=wait, daemon=True)
            th.start()
            with self._lock:
                self.saves.append(rec)
                self._watchers.append(th)
            return h
        engine.save = wrapped

    def join(self, timeout=600):
        for th in self._watchers:
            th.join(timeout)
            if th.is_alive():
                raise RuntimeError("a save did not commit in time")

    def reset(self):
        self.t = {}


def blocked_seconds(spans: Spans) -> float:
    """Time the train loop was held by checkpointing: inside
    ``engine.save`` (its throttle included) and ``wait_snapshot``."""
    return spans.t.get("engine.save", 0.0) + spans.t.get("wait_snapshot", 0.0)


# ------------------------------------------------------------ compiles
class CompileLog:
    """Counts of JAX's persistent-cache events and the seconds spent
    reading the cache, from ``jax.monitoring``: set-up should find every
    program there after a checkout's first run, and the window should
    ask for none."""

    PREFIX = "/jax/compilation_cache/"

    def __init__(self):
        import jax.monitoring as mon
        self.n, self.s = {}, {}
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **kw):
        if name.startswith(self.PREFIX):
            k = name[len(self.PREFIX):]
            self.n[k] = self.n.get(k, 0) + 1

    def _duration(self, name, secs, **kw):
        if name.startswith(self.PREFIX):
            k = name[len(self.PREFIX):]
            self.s[k] = self.s.get(k, 0.0) + secs

    def snapshot(self) -> tuple:
        return self.n.get("compile_requests_use_cache", 0), \
            self.n.get("cache_hits", 0), self.n.get("cache_misses", 0), \
            self.s.get("cache_retrieval_time_sec", 0.0)

    def since(self, before: tuple) -> str:
        now = self.snapshot()
        req, hit, miss, sec = (a - b for a, b in zip(now, before))
        return (f"compile requests {req}, cache hits {hit}, misses {miss}, "
                f"cache read {sec:.3f}s")


# ------------------------------------------------------------ disk use
def clean_ckpt():
    shutil.rmtree(CKPT_DIR, ignore_errors=True)


def evict(directory):
    """Drop a checkpoint's files from the page cache (a resume on a
    replaced node reads from the disk, not from memory)."""
    for d, _, files in os.walk(directory):
        for f in files:
            fd = os.open(os.path.join(d, f), os.O_RDONLY)
            try:
                os.fsync(fd)
                os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
            finally:
                os.close(fd)


# -------------------------------------------------------- comparisons
def leaf_bytes(x) -> np.ndarray:
    return np.ascontiguousarray(np.atleast_1d(np.asarray(x))).view(np.uint8)


def differing_bytes(got, want) -> int:
    """Bytes that differ between two trees of equal structure, one leaf
    on the host at a time; a structure or size mismatch counts every
    byte of the larger side."""
    import jax
    g, w = jax.tree.leaves(got), jax.tree.leaves(want)
    if jax.tree.structure(got) != jax.tree.structure(want) or len(g) != len(w):
        return sum(leaf_bytes(x).size for x in w) or 1
    n = 0
    for a, b in zip(g, w):
        a, b = leaf_bytes(a), leaf_bytes(b)
        n += (int(np.count_nonzero(a != b)) if a.size == b.size
              else max(a.size, b.size))
    return n


def program_readings(tr, m: dict, o: dict, wseed: int, check_steps: int,
                     start: int):
    """Drive the trainer through its first ``check_steps`` steps through
    its own ``run`` and read what the reference is compared on: each
    loss, the per-leaf norms of the first clipped gradient (the first
    moment after one step from zero, over 1 - b1), and the per-leaf
    norms of the master's change over all steps."""
    import jax
    import jax.numpy as jnp
    from reference import init_params, leaf_norms

    tr.cfg.steps = start + 1
    tr.run(start_step=start)
    b1 = o["b1"]
    grad = jax.jit(lambda mom: leaf_norms(jax.tree.map(
        lambda x: x / (1 - b1), mom)))(tr.state.opt.m)
    grad = [float(x) for x in grad]
    tr.cfg.steps = start + check_steps
    tr.run(start_step=start + 1)
    change = jax.jit(lambda mw: leaf_norms(jax.tree.map(
        lambda a, b: a - b.astype(jnp.bfloat16).astype(jnp.float32), mw,
        init_params(m, wseed))))(tr.state.opt.master)
    loss = [float(r.loss) for r in tr.history[-check_steps:]]
    return {"loss": loss, "grad": grad,
            "change": [float(x) for x in change]}


def train_gaps(prog: dict, ref: dict, skip_frac: float) -> dict:
    """The numbers compared: the worst step's loss gap, and the worst
    leaf's gap of first-gradient and three-step-change norms. Leaves
    whose reference gradient is under ``skip_frac`` of the median
    leaf's move under Adam by round-off alone and are left out of the
    change."""
    from reference import worst_leaf_gap
    med = float(np.median(ref["grad"]))
    skip = [i for i, g in enumerate(ref["grad"]) if g < skip_frac * med]
    return {
        "loss_gap": max(abs(a - b) / abs(b)
                        for a, b in zip(prog["loss"], ref["loss"])),
        "grad_gap": float(worst_leaf_gap(prog["grad"], ref["grad"])),
        "change_gap": float(worst_leaf_gap(prog["change"], ref["change"],
                                           skip)),
    }


def verdict(readings: dict, limits: dict):
    """(correct, checks): every reading at or under its limit."""
    checks = {k: {"value": readings[k], "limit": limits[k]}
              for k in limits}
    ok = all(v["value"] is not None and v["value"] <= v["limit"]
             for v in checks.values())
    return ok, checks
