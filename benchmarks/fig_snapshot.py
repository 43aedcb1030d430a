"""Chunked device→arena snapshots + device-side dirty masks
(DESIGN.md §10): step-boundary stall and device→host traffic.

Two sweeps, one per §10 leg:

  * **chunk-size sweep** — the §4.3 training cadence (submit after the
    optimizer, compute the next iteration, sync before the next
    optimizer) against an async engine, monolithic snapshot vs chunked.
    The measured quantity is the *step-boundary stall*: main-thread time
    blocked in ``save()`` (the commit throttle) plus ``wait_snapshot()``
    (the donation gate). Chunking overlaps the D2H copy with the NVMe
    writes, so the commit lands ~max(copy, write) after submit instead
    of copy + write — the throttle shrinks. ``stall_x`` (monolithic over
    chunked, at the default 8 MiB chunk) is the headline; >= 2x is the
    acceptance bar. The device→host leg runs behind an emulated link
    (``_EmuDeviceBlob``) calibrated to the measured disk bandwidth —
    see its docstring for why a CPU-only host needs one.
  * **dirty-fraction sweep** — delta chains over a float32 blob with the
    Pallas change-mask kernel (``device_dirty``) vs the host byte
    compare. ``pcie_x`` = device→host bytes of the delta saves over the
    bytes actually dirtied; the masks ride along, so <= 1.2x at 1% dirty
    is the bar (the host-compare baseline moves the WHOLE stream every
    save). Bit-exact restores are asserted per cell.

Rows are persisted to ``experiments/fig_snapshot.json`` and folded into
the EXPERIMENTS tables by ``benchmarks.make_tables``.
"""
import json
import os
import shutil
import time

import numpy as np

from benchmarks.common import bench_dir, cleanup, emit
from repro.core.checkpointer import FastPersistConfig
from repro.core.engine import CheckpointEngine, CheckpointSpec

PAGE = 4096


def _spec(d, chunk_mb, **fp_kw):
    return CheckpointSpec(
        directory=d, backend="fastpersist-pipelined",
        fp=FastPersistConfig(strategy="replica",
                             snapshot_chunk_mb=chunk_mb, **fp_kw))


class _EmuDeviceBlob:
    """A device-resident tensor behind an emulated device→host link.

    This container has no accelerator: a "D2H copy" here is a plain
    memcpy at memory-bus speed (~5 GB/s) while the virtual disk writes
    at ~0.3 GB/s — a 15:1 copy:write ratio the paper's hardware never
    sees (PCIe ~12-25 GB/s against NVMe arrays aggregated to the same
    order, §4.1). With the copy that lopsided there is nothing for the
    chunk pipeline to overlap, so the sweep would measure the host's
    memory bus, not §10. This wrapper restores the paper's regime:
    every byte-range read charges its transfer time at ``rate`` bytes/s
    as a GIL-released sleep — the CPU stays as free as it would behind
    a real DMA engine — and the rate is calibrated against the measured
    write bandwidth so copy ≈ write (Eq. 1's boundary). The chunked
    fill takes each leaf whole through ``__array__``, so it pays the
    link once a leaf, like a whole-leaf D2H."""

    def __init__(self, host: np.ndarray, rate: float):
        self.host = host
        self.rate = float(rate)
        self.dtype = host.dtype
        self.shape = host.shape
        self.size = host.size
        self.nbytes = host.nbytes

    def __array__(self, dtype=None):
        time.sleep(self.nbytes / self.rate)
        h = self.host
        return h if dtype is None else h.astype(dtype)


def _stall_loop(d, chunk_mb, state, steps, compute_s):
    """§4.3 cadence; returns median per-step stall seconds (submit
    throttle + snapshot gate) and the final-restore check."""
    shutil.rmtree(d, ignore_errors=True)
    # mutations and the restore compare go through the backing host
    # array: the emulated link only meters the engine's reads
    raw = getattr(state["blob"], "host", state["blob"])
    stalls = []
    with CheckpointEngine(_spec(d, chunk_mb)) as eng:
        eng.save(state, 0).wait()           # prime arena + plan cache
        for step in range(1, steps + 1):
            # mutate first (the optimizer step) — the previous
            # iteration's wait_snapshot made this safe
            raw[step % raw.size] ^= 0x5A
            t0 = time.perf_counter()
            eng.save(state, step)           # blocks on previous commit
            t1 = time.perf_counter()
            time.sleep(compute_s)           # next iteration's fwd+bwd
            t2 = time.perf_counter()
            eng.wait_snapshot()             # donation gate (§10)
            t3 = time.perf_counter()
            stalls.append((t1 - t0) + (t3 - t2))
        eng.wait()
        restored, _ = eng.load(steps, like=state)
        ok = all(np.array_equal(np.asarray(restored[k]),
                                getattr(state[k], "host", state[k]))
                 for k in state)
    shutil.rmtree(d, ignore_errors=True)
    return float(np.median(stalls)), ok


def _stall_sweep(d, chunks, state, steps, compute_s, reps):
    """Round-robin the chunk cells ``reps`` times and take per-cell
    medians. Sequential per-cell blocks are NOT comparable on a real
    disk: writeback debt accumulates over the run and the kernel
    throttles later cells progressively, so every cell must sample
    every phase of the drift. ``os.sync()`` before each loop drains the
    debt the previous loop left behind."""
    stalls = {c: [] for c in chunks}
    oks = {c: True for c in chunks}
    for _rep in range(reps):
        for chunk_mb in chunks:
            os.sync()
            s, ok = _stall_loop(os.path.join(d, f"c{chunk_mb}"), chunk_mb,
                                state, steps, compute_s)
            stalls[chunk_mb].append(s)
            oks[chunk_mb] = oks[chunk_mb] and ok
    return ({c: float(np.median(v)) for c, v in stalls.items()}, oks)


def _touch_pages(w, rng, dirty_frac):
    """Rewrite ``dirty_frac`` of the blob's 4 KiB pages in place;
    returns the bytes dirtied."""
    pages = w.nbytes // PAGE
    n = max(1, int(pages * dirty_frac))
    idx = rng.choice(pages, size=n, replace=False)
    f32_per_page = PAGE // 4
    for p in idx:
        w[p * f32_per_page:(p + 1) * f32_per_page] += 1.0
    return n * PAGE


def _pcie_loop(d, device_dirty, mb, steps, dirty_frac):
    """Delta chain over a float32 blob; returns (delta d2h bytes,
    dirtied bytes, keyframe d2h bytes, bit-exact)."""
    shutil.rmtree(d, ignore_errors=True)
    rng = np.random.default_rng(23)
    w = rng.standard_normal(mb * (1 << 20) // 4).astype(np.float32)
    state = {"w": w, "ctr": np.zeros(1, np.int32)}
    d2h_delta, dirty_bytes = 0, 0
    with CheckpointEngine(_spec(d, 8, keyframe_every=steps + 2,
                                device_dirty=device_dirty)) as eng:
        kf = eng.save(state, 0).wait()      # keyframe: full D2H
        for step in range(1, steps + 1):
            dirty_bytes += _touch_pages(w, rng, dirty_frac)
            state["ctr"] += 1
            dirty_bytes += state["ctr"].nbytes
            st = eng.save(state, step).wait()
            assert st.delta is not None, "delta chain broke"
            d2h_delta += st.d2h_bytes
        restored, _ = eng.load(steps, like=state)
        ok = all(np.array_equal(np.asarray(restored[k]), state[k])
                 for k in state)
    shutil.rmtree(d, ignore_errors=True)
    return d2h_delta, dirty_bytes, kf.d2h_bytes, ok


def run(quick=True, mb=32, smoke=False):
    steps = 3 if smoke else (6 if quick else 10)
    if smoke:
        mb = min(mb, 8)
    # the dirty sweep runs the Pallas kernel in interpret mode on CPU
    # hosts — one Python-level grid step per 4 KiB block — so quick runs
    # cap ITS blob (the pcie_x ratio is size-independent: mask overhead
    # over dirty bytes); the stall sweep keeps the full size
    dirty_mb = min(mb, 8) if quick else mb
    # the stall sweep runs bigger: millisecond-scale per-save times for
    # a small state collapse into scheduler noise
    stall_mb = mb if smoke else mb * 4
    d = os.path.join(bench_dir(), "fsnap")
    out = {"mb": mb, "stall_mb": stall_mb, "dirty_mb": dirty_mb,
           "steps": steps, "chunk_cells": [], "dirty_cells": []}

    # ---- chunk-size sweep: step-boundary stall vs monolithic --------
    blob = np.frombuffer(
        bytearray(os.urandom(stall_mb << 20)), dtype=np.uint8).copy()
    state = {"blob": blob, "step_ctr": np.zeros(1, np.int64)}
    # calibrate: a raw-numpy prime measures the disk's steady-state
    # write time and the memcpy share of the copy, then the emulated
    # device link (see _EmuDeviceBlob) is rated so copy ≈ write and the
    # compute window is sized to Eq. 1's boundary — the OVERLAPPED save
    # (max(copy, write)) fits inside fwd+bwd, the serial one
    # (copy + write) does not
    os.sync()
    memcpys, writes = [], []
    with CheckpointEngine(_spec(os.path.join(d, "prime"), 0)) as eng:
        eng.save(state, 0).wait()           # cold: layout + allocation
        for i in range(1, 4):               # warm arena = steady state
            blob[i] ^= 1
            pst = eng.save(state, i).wait()
            memcpys.append(pst.serialize_seconds)
            writes.append(pst.seconds)
    shutil.rmtree(os.path.join(d, "prime"), ignore_errors=True)
    memcpy_s, write_s = float(np.median(memcpys)), float(np.median(writes))
    rate = blob.nbytes / max(write_s - memcpy_s, 1e-3)
    state = {"blob": _EmuDeviceBlob(blob, rate),
             "step_ctr": state["step_ctr"]}
    copies = []
    with CheckpointEngine(_spec(os.path.join(d, "prime"), 0)) as eng:
        eng.save(state, 0).wait()
        for i in range(1, 3):               # measured copy incl. link
            blob[i] ^= 1
            copies.append(eng.save(state, i).wait().serialize_seconds)
    shutil.rmtree(os.path.join(d, "prime"), ignore_errors=True)
    copy_s = float(np.median(copies))
    compute_s = max(copy_s, write_s) + 0.25 * min(copy_s, write_s)
    out["compute_window_ms"] = round(compute_s * 1e3, 3)
    out["prime_copy_ms"] = round(copy_s * 1e3, 3)
    out["prime_write_ms"] = round(write_s * 1e3, 3)
    out["emu_link_gbps"] = round(rate / 1e9, 3)

    chunks = [0, 2] if smoke else ([0, 2, 8] if quick else [0, 1, 2, 4, 8,
                                                            16])
    reps = 1 if smoke else (3 if quick else 5)
    medians, oks = _stall_sweep(os.path.join(d, "stall"), chunks, state,
                                steps, compute_s, reps)
    stall_mono = medians[0]
    for chunk_mb in chunks:
        stall, ok = medians[chunk_mb], oks[chunk_mb]
        cell = {"chunk_mb": chunk_mb, "stall_ms": round(stall * 1e3, 3),
                "ok": bool(ok)}
        if chunk_mb != 0:
            cell["stall_x"] = round(stall_mono / max(stall, 1e-6), 2)
        emit(f"fig_snapshot/chunk{chunk_mb}", stall,
             f"{cell.get('stall_x', 1.0)}x_stall,ok={ok}")
        out["chunk_cells"].append(cell)

    # ---- dirty-fraction sweep: PCIe bytes, device masks vs host -----
    fracs = [0.01] if smoke else [0.01, 0.1]
    for frac in fracs:
        dd, dirty, kf_d2h, ok_dev = _pcie_loop(
            os.path.join(d, f"dev{frac}"), True, dirty_mb, steps, frac)
        hd, _, _, ok_host = _pcie_loop(
            os.path.join(d, f"host{frac}"), False, dirty_mb, steps, frac)
        cell = {"dirty_frac": frac,
                "d2h_device": dd, "d2h_host": hd,
                "dirty_bytes": dirty,
                "pcie_x": round(dd / max(dirty, 1), 3),
                "host_x": round(hd / max(dirty, 1), 2),
                "ok": bool(ok_dev and ok_host)}
        emit(f"fig_snapshot/dirty{frac}", 0.0,
             f"{cell['pcie_x']}x_dirty_bytes,host={cell['host_x']}x")
        out["dirty_cells"].append(cell)

    # the default chunk size (8 MiB) is the headline cell; smoke runs
    # sweep smaller sizes, so fall back to the largest chunked cell
    default_x = next(
        (c.get("stall_x", 0.0) for c in out["chunk_cells"]
         if c["chunk_mb"] == 8),
        max((c.get("stall_x", 0.0) for c in out["chunk_cells"]), default=0.0))
    sparse = next((c for c in out["dirty_cells"]
                   if c["dirty_frac"] <= 0.01), {})
    all_ok = all(c["ok"] for c in out["chunk_cells"] + out["dirty_cells"])
    out["default_chunk_stall_x"] = default_x
    out["sparse_pcie_x"] = sparse.get("pcie_x", float("inf"))
    out["verdict"] = ("supported" if default_x >= 2.0
                      and out["sparse_pcie_x"] <= 1.2 and all_ok
                      else "refuted")
    emit("fig_snapshot/verdict", 0.0, out["verdict"])
    shutil.rmtree(d, ignore_errors=True)
    if not smoke:
        os.makedirs("experiments", exist_ok=True)
        with open("experiments/fig_snapshot.json", "w") as f:
            json.dump(out, f, indent=2)
    return out


if __name__ == "__main__":
    print(json.dumps(run(), indent=2))
    cleanup()
