#!/usr/bin/env python3
"""Smoke run of training with per-step checkpoints on a TPU.

    python chip_smoke.py              # one chip: phases (a)-(e) below
    python chip_smoke.py --chips 4    # four chips: sharded save/restore

One chip drives the main path through ``Trainer`` and
``CheckpointEngine`` with the paper's smallest model, gpt3_0_7b, at its
published widths (d_model 1536, 16 heads, d_ff 6144, vocab 50257) and
random weights made from ``--seed``:

  (a) device: a TPU is required; prints the chip, host RAM and the
      checkpoint filesystem's free space;
  (b) six train steps, each saved through the pipelined backend;
  (c) step 6 loaded into host buffers, compared bit for bit with the
      live device state;
  (d) a fresh Trainer resumes from step 4 and retrains steps 5-6: the
      losses must equal (b)'s exactly;
  (e) keyframe + three deltas with device-side dirty masks (the
      ckpt_pack_dirty Mosaic kernel), restored bit-exact, with fewer
      bytes crossing device->host than the stream holds.

``--chips 4`` instead builds the full-depth state sharded over a
four-device mesh, saves it, restores it onto the same shardings and onto
one chip, and compares both against the same state saved and restored
on one chip.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``, printed only
when every phase passed. Without a TPU the script exits non-zero before
any phase runs.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

ARCH = "gpt3_0_7b"
#: depth of phases (b)-(d). HBM alone would allow 18 of 24 layers:
#: gpt3_0_7b's train step at batch 2x1024 compiles for one v5e up to 18
#: (19: "Used 16.42G of 15.75G hbm"). The bound is disk: the whole run
#: is kept under 45 GiB written, deleted files included. Six saves of
#: 12 layers write 35.1 GB, phase (e) 7.3 GB more; (d) therefore
#: resumes without saving.
LAYERS = 12
#: depth of phase (e): small enough that state + device-resident image
#: fit easily, and its keyframe + 3 deltas stay inside the write budget
DIRTY_LAYERS = 4
BATCH, SEQ = 2, 1024


class Failed(Exception):
    pass


def check(cond, what):
    if not cond:
        raise Failed(what)


def log(*a):
    print(*a, flush=True)


class CompileCounter:
    """Executables built (compiled, or loaded from the persistent
    cache), from JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_kw):
        if event == self.EVENT:
            self.n += 1


# ------------------------------------------------------------- helpers
def _bytes(x):
    import numpy as np
    return np.ascontiguousarray(np.atleast_1d(np.asarray(x))).view(np.uint8)


def compare_leaves(got, want, what):
    """Bit-for-bit, one leaf at a time (one leaf on the host at once)."""
    import jax
    import numpy as np
    g, gdef = jax.tree_util.tree_flatten_with_path(got)
    w, wdef = jax.tree.flatten(want)
    check(gdef == wdef, f"{what}: tree structure differs")
    for (path, a), b in zip(g, w):
        check(np.array_equal(_bytes(a), _bytes(b)),
              f"{what}: {jax.tree_util.keystr(path)} differs")
    return len(w)


def stream_bytes(state):
    import jax
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(state))


def fmt_gb(n):
    return f"{n / 1e9:.3f} GB"


def host_ram():
    with open("/proc/meminfo") as f:
        info = dict(line.split(":", 1) for line in f)
    kb = lambda k: int(info[k].split()[0]) * 1024  # noqa: E731
    return kb("MemTotal"), kb("MemAvailable")


# ----------------------------------------------------------- (a) device
def require_tpu(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {devs[0].platform!r}"
                 f" ({len(devs)} device(s)); nothing was run")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU chips, "
                 f"JAX found {len(devs)}")
    return devs


def phase_a(devs, root, need_bytes):
    total, avail = host_ram()
    os.makedirs(root, exist_ok=True)
    free = shutil.disk_usage(root).free
    log(f"(a) device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}"
        f"; host RAM {fmt_gb(total)} ({fmt_gb(avail)} available)"
        f"; {root}: {fmt_gb(free)} free, {fmt_gb(need_bytes)} needed")
    check(free >= need_bytes, f"{root} has {fmt_gb(free)} free; the "
          f"retained checkpoints need {fmt_gb(need_bytes)}")


# ------------------------------------------------ one chip: (b)-(e)
def trainer_config(cfg, directory, steps, seed, batch, seq, every=1,
                   **policy):
    from repro.core.checkpointer import FastPersistConfig
    from repro.optim.adam import AdamConfig
    from repro.train.trainer import CheckpointPolicy, TrainerConfig
    opt = policy.pop("opt", AdamConfig())
    fp = FastPersistConfig(device_dirty=policy.pop("device_dirty", False))
    return TrainerConfig(
        model=cfg, steps=steps, global_batch=batch, seq_len=seq, seed=seed,
        opt=opt, log_every=10 ** 9,
        checkpoint=CheckpointPolicy(directory=directory, every=every,
                                    backend="fastpersist-pipelined", fp=fp,
                                    **policy))


def save_line(rec):
    st = rec.save.result()
    subs = sorted({w.backend for w in st.per_writer})
    direct = all(w.direct for w in st.per_writer)
    return (f"loss={float(rec.loss)!r} ckpt_stall={rec.ckpt_stall:.4f}s "
            f"save={fmt_gb(st.total_bytes)} in {st.seconds:.3f}s "
            f"writers={st.n_writers} submitter={'/'.join(subs)} "
            f"O_DIRECT={'yes' if direct else 'no'}")


def phase_b(cfg, root, seed, batch, seq, counter):
    from repro.core.retention import RetentionPolicy
    from repro.train.trainer import Trainer
    tr = Trainer(trainer_config(
        cfg, root, 6, seed, batch, seq,
        retention=RetentionPolicy(keep_last=1, keep_every=4)))
    c0 = counter.n
    t0 = time.perf_counter()
    tr.run()
    log(f"(b) 6 steps in {time.perf_counter() - t0:.1f}s, "
        f"{counter.n - c0} executables built")
    for rec in tr.history:
        log(f"(b) step {rec.step}: {save_line(rec)}")
    losses = {r.step: float(r.loss) for r in tr.history}
    for s, v in losses.items():
        check(v == v and abs(v) < 1e4, f"(b) step {s} loss {v} not finite")
    check(4 in tr.engine.steps() and tr.engine.latest_step() == 6,
          f"(b) retained steps {tr.engine.steps()} lack 4 or 6")
    return tr, losses


def phase_c(tr):
    t0 = time.perf_counter()
    restored, manifest = tr.engine.load(6, like=tr.state, parallel="auto")
    check(manifest.extras.get("step") == 6, "(c) manifest step != 6")
    n = compare_leaves(restored, tr.state, "(c) restore of step 6")
    log(f"(c) step 6 loaded to host in parallel and compared: {n} leaves, "
        f"{fmt_gb(stream_bytes(tr.state))}, bit-exact "
        f"({time.perf_counter() - t0:.1f}s)")


def phase_d(cfg, root, seed, batch, seq, losses_b, device):
    """Resume from step 4 and retrain 5-6, saving nothing (the write
    budget, see LAYERS)."""
    from repro.train.trainer import Trainer
    tr = Trainer(trainer_config(cfg, root, 6, seed, batch, seq, every=7))
    t0 = time.perf_counter()
    start = tr.restore(step=4)
    check(start == 4, f"(d) restore returned step {start}")
    log(f"(d) fresh Trainer restored step 4 in "
        f"{time.perf_counter() - t0:.1f}s")
    tr.run(start_step=start)
    got = {r.step: float(r.loss) for r in tr.history}
    for s in (5, 6):
        check(got.get(s) == losses_b[s],
              f"(d) resumed step {s} loss {got.get(s)!r} != "
              f"uninterrupted {losses_b[s]!r}")
    log(f"(d) resumed losses equal the uninterrupted run: "
        f"5={got[5]!r} 6={got[6]!r}")
    stats = device.memory_stats() if device is not None else None
    if stats and "peak_bytes_in_use" in stats:
        log(f"(d) device peak in use {fmt_gb(stats['peak_bytes_in_use'])}"
            f" of {fmt_gb(stats.get('bytes_limit', 0))}")
    return tr


def kernel_is_compiled(leaf, block):
    """True when ckpt_pack_dirty lowers to a Mosaic custom call here."""
    from repro.kernels import ops
    prev = ops.pack_blocks(leaf, block=block)
    text = ops.ckpt_pack_dirty.lower(leaf, prev, block=block) \
        .compile().as_text()
    return "tpu_custom_call" in text


def phase_e(cfg, root, seed, batch, seq, counter):
    """Keyframe + three deltas with device dirty masks. The optimizer
    runs with lr=0, so params and the fp32 master are byte-identical
    between saves while both Adam moments change: the mask must skip
    the former and ship the latter."""
    import jax
    from repro.optim.adam import AdamConfig
    from repro.train.trainer import Trainer
    tr = Trainer(trainer_config(cfg, root, 1, seed, batch, seq,
                                keyframe_every=4, device_dirty=True,
                                opt=AdamConfig(lr=0.0)))
    for step in range(1, 5):
        c0 = counter.n
        tr.cfg.steps = step
        tr.run(start_step=step - 1)          # drains: the save committed
        rec = tr.history[-1]
        st = rec.save.result()
        total = stream_bytes(tr.state)
        kind = "delta" if st.delta is not None else "keyframe"
        log(f"(e) step {step} {kind}: loss={float(rec.loss)!r} "
            f"d2h={st.d2h_bytes} of {total} stream bytes "
            f"({st.d2h_bytes / total:.4f}), wrote {st.total_bytes}, "
            f"{counter.n - c0} executables built")
        check((kind == "delta") == (step > 1),
              f"(e) step {step} is a {kind}")
        if step > 1:
            check(st.d2h_bytes < total, f"(e) delta step {step} moved "
                  f"{st.d2h_bytes} >= {total} bytes device->host")
    float_leaf = next(x for x in jax.tree.leaves(tr.state)
                      if x.dtype.name == "float32" and x.size >= 1024)
    check(kernel_is_compiled(float_leaf, 1024),
          "(e) ckpt_pack_dirty did not lower to a Mosaic kernel")
    restored, _ = tr.engine.load(4, like=tr.state, parallel="auto")
    n = compare_leaves(restored, tr.state, "(e) delta-chain restore")
    log(f"(e) keyframe+3 deltas restored bit-exact ({n} leaves); "
        f"ckpt_pack_dirty compiled to a Mosaic kernel")
    return tr


def run_one_chip(cfg, dirty_cfg, root, seed, batch=BATCH, seq=SEQ,
                 device=None):
    counter = CompileCounter()
    dir_b = os.path.join(root, "train")
    dir_e = os.path.join(root, "dirty")
    for d in (dir_b, dir_e):
        shutil.rmtree(d, ignore_errors=True)
    log(f"(b) {cfg.name}: d_model={cfg.d_model} heads={cfg.n_heads} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab_size}, {cfg.n_layers} layers, "
        f"batch {batch}x{seq}, {fmt_gb(cfg.checkpoint_bytes())} of state")
    tr, losses = phase_b(cfg, dir_b, seed, batch, seq, counter)
    phase_c(tr)
    del tr
    gc.collect()
    tr = phase_d(cfg, dir_b, seed, batch, seq, losses, device)
    del tr
    gc.collect()
    shutil.rmtree(dir_b, ignore_errors=True)
    tr = phase_e(dirty_cfg, dir_e, seed, batch, seq, counter)
    del tr
    gc.collect()
    shutil.rmtree(dir_e, ignore_errors=True)


# ----------------------------------------------------- four chips
def shardings_for(abstract, mesh, axis="data"):
    """Shard every leaf on its first axis the mesh divides; replicate
    the rest (the int32 step, odd-sized vectors)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    n = mesh.shape[axis]

    def spec(a):
        for i, d in enumerate(a.shape):
            if d % n == 0:
                return NamedSharding(mesh, P(*([None] * i), axis))
        return NamedSharding(mesh, P())
    return jax.tree.map(spec, abstract)


def check_placement(state, targets, what):
    import jax
    for (path, x), t in zip(jax.tree_util.tree_flatten_with_path(state)[0],
                            jax.tree.leaves(targets)):
        check(x.sharding.device_set == t.device_set
              and x.sharding.is_equivalent_to(t, x.ndim),
              f"{what}: {jax.tree_util.keystr(path)} on "
              f"{x.sharding} not {t}")


def run_four_chips(cfg, root, seed, devs):
    import jax
    from jax.sharding import SingleDeviceSharding
    from repro.core.engine import CheckpointEngine, CheckpointSpec
    from repro.launch.mesh import make_mesh
    from repro.models.registry import build_model
    from repro.train.steps import init_train_state

    model = build_model(cfg)
    key = jax.random.PRNGKey(seed)
    init = lambda k: init_train_state(model, k)  # noqa: E731
    abstract = jax.eval_shape(init, key)
    mesh = make_mesh((len(devs),), ("data",), devices=devs)
    sharded = shardings_for(abstract, mesh)
    one = SingleDeviceSharding(devs[0])
    total = stream_bytes(abstract)
    dir_a = os.path.join(root, "sharded")
    dir_r = os.path.join(root, "one_chip")
    for d in (dir_a, dir_r):
        shutil.rmtree(d, ignore_errors=True)
    engine_a = CheckpointEngine(CheckpointSpec(directory=dir_a))
    engine_r = CheckpointEngine(CheckpointSpec(directory=dir_r))
    log(f"(4) {cfg.name} full depth {cfg.n_layers}: {fmt_gb(total)} of "
        f"state over {mesh}")

    state = jax.jit(init, out_shardings=sharded)(key)
    check_placement(state, sharded, "(4) built state")
    t0 = time.perf_counter()
    st = engine_a.save(state, 1).result()
    log(f"(4) sharded state saved: {fmt_gb(st.total_bytes)} in "
        f"{time.perf_counter() - t0:.1f}s")
    for target, name in ((sharded, "4-chip shardings"), (one, "one chip")):
        t0 = time.perf_counter()
        back, _ = engine_a.load(1, like=abstract, sharding=target,
                                parallel="auto")
        jax.block_until_ready(back)
        dt = time.perf_counter() - t0
        tgt = target if target is not one else jax.tree.map(
            lambda _: one, abstract)
        check_placement(back, tgt, f"(4) restore onto {name}")
        n = compare_leaves(back, state, f"(4) restore onto {name}")
        log(f"(4) restore onto {name}: {n} leaves bit-exact, each on its "
            f"target devices ({dt:.1f}s)")
        del back
        gc.collect()
    del state
    gc.collect()

    ref = jax.jit(init, out_shardings=one)(key)
    engine_r.save(ref, 1).result()
    host_a, _ = engine_a.load(1, like=abstract, parallel="auto")
    compare_leaves(host_a, ref, "(4) 4-chip checkpoint vs one-chip state")
    del ref
    gc.collect()
    back, _ = engine_r.load(1, like=abstract, sharding=one, parallel="auto")
    check_placement(back, jax.tree.map(lambda _: one, abstract),
                    "(4) one-chip restore")
    compare_leaves(back, host_a, "(4) one-chip save/restore vs 4-chip "
                   "checkpoint")
    log("(4) the same state built, saved and restored on one chip equals "
        "the 4-chip checkpoint bit for bit")
    del back, host_a
    for e in (engine_a, engine_r):
        e.close()
    for d in (dir_a, dir_r):
        shutil.rmtree(d, ignore_errors=True)


# ------------------------------------------------------------------ main
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded save/restore path")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=os.path.join(HERE, ".ckpt_smoke"))
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    devs = require_tpu(args.chips)
    from repro.configs import get_config
    cfg = get_config(ARCH)
    if args.chips == 4:
        devs = devs[:4]
        phase_a(devs, args.ckpt_dir, 2.2 * cfg.checkpoint_bytes())
        run_four_chips(cfg, args.ckpt_dir, args.seed, devs)
    else:
        train_cfg = dataclasses.replace(cfg, n_layers=LAYERS)
        dirty_cfg = dataclasses.replace(cfg, n_layers=DIRTY_LAYERS)
        # (b) retains step 4 + the newest, plus one save in flight
        phase_a(devs, args.ckpt_dir, 3.1 * train_cfg.checkpoint_bytes())
        writes = (6 * train_cfg.checkpoint_bytes()
                  + 2.8 * dirty_cfg.checkpoint_bytes())
        log(f"depth cut: {ARCH} {cfg.n_layers} -> {LAYERS} layers for "
            f"(b)-(d), {DIRTY_LAYERS} for (e); widths as published; "
            f"about {fmt_gb(writes)} written in all")
        run_one_chip(train_cfg, dirty_cfg, args.ckpt_dir, args.seed,
                     device=devs[0])
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    try:
        main()
    except Failed as e:
        sys.exit(f"chip_smoke: FAILED: {e}")
