"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch stablelm_1_6b \
        --reduced --steps 50 --batch 8 --seq 128 \
        --ckpt-dir /tmp/ckpt --ckpt-mode fastpersist --every 1 --pipeline

On a CPU host use --reduced; on one TPU chip the paper's models
(``--arch gpt3_0_7b`` etc.) run at their published widths with depth
cut to fit (see chip_smoke.py).
"""
from __future__ import annotations

import argparse
import time

from repro.configs import get_config, reduced as make_reduced
from repro.core.checkpointer import FastPersistConfig
from repro.core.partition import Topology
from repro.core.writer import WriterConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.optim.adam import AdamConfig
from repro.train.trainer import CheckpointPolicy, Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--gas", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-mode", default="fastpersist",
                    choices=["fastpersist", "baseline", "none"])
    ap.add_argument("--backend", default=None,
                    help="explicit CheckpointEngine backend name "
                         "(overrides --ckpt-mode/--pipeline); see "
                         "repro.core.engine.available_backends()")
    ap.add_argument("--every", type=int, default=1)
    ap.add_argument("--keyframe-every", type=int, default=1,
                    help="incremental delta checkpoints: every Nth save "
                         "is a full keyframe, the rest write only the "
                         "byte ranges that changed since the previous "
                         "save (1 = every save is full). Needs the "
                         "serialize arena (incompatible with --no-arena)")
    ap.add_argument("--delta-quantize", action="store_true",
                    help="int8-quantize delta spans (lossy; blockwise "
                         "absmax scales, DESIGN.md §9) — keyframes stay "
                         "full-precision")
    ap.add_argument("--delta-stripe-min-mb", type=int, default=8,
                    help="stripe a delta generation across the full "
                         "writer/volume fan-out once its packed payload "
                         "reaches this many MiB (DESIGN.md §13); smaller "
                         "deltas single-stream into the primary so tiny "
                         "writes skip per-volume fsync overhead (0 = "
                         "always stripe)")
    ap.add_argument("--pipeline", action="store_true", default=True)
    ap.add_argument("--no-pipeline", dest="pipeline", action="store_false")
    ap.add_argument("--writers", default="auto",
                    choices=["auto", "replica", "socket"])
    ap.add_argument("--dp", type=int, default=4,
                    help="simulated DP degree for checkpoint writers")
    ap.add_argument("--volumes", default=None,
                    help="comma-separated shard destination volume roots "
                         "(one per SSD/mount); shards are striped across "
                         "them, manifest+COMMIT stay under --ckpt-dir")
    ap.add_argument("--io-backend", default="auto",
                    choices=["auto", "io_uring", "libaio", "pwrite"],
                    help="write-submission backend (capability-probed; "
                         "unavailable backends fall back to pwrite; "
                         "$FASTPERSIST_IO_BACKEND overrides)")
    ap.add_argument("--queue-depth", type=int, default=2,
                    help="in-flight writes per writer stream; staging "
                         "memory is (depth+1) x io buffer per writer")
    ap.add_argument("--snapshot-chunk-mb", type=int, default=8,
                    help="chunk size for the overlapped device→arena "
                         "snapshot stage (DESIGN.md §10): NVMe writers "
                         "start as soon as the first chunk lands. 0 = "
                         "monolithic snapshot. Needs the serialize arena")
    ap.add_argument("--device-dirty", action="store_true",
                    help="compute delta-checkpoint dirty masks ON DEVICE "
                         "(Pallas pack+compare kernel) so delta saves "
                         "transfer only dirty blocks over PCIe; costs one "
                         "device-resident copy of the packed state. "
                         "Implies dirty tracking via --keyframe-every")
    ap.add_argument("--no-arena", dest="arena", action="store_false",
                    default=True,
                    help="disable the persistent serialize arena "
                         "(allocate fresh host buffers every save)")
    ap.add_argument("--upload-store", default=None,
                    help="object-store spec for the second durability "
                         "tier (a directory path or file:// URL uses the "
                         "built-in mock bucket; registered scheme:// URLs "
                         "reach real stores). Selects the "
                         "fastpersist-tiered backends: sealed shards "
                         "stream to the store AFTER each local commit, "
                         "and --restore falls back to the store when the "
                         "local checkpoint directory is empty/lost")
    ap.add_argument("--peers", default=None,
                    help="comma-separated peer-replication targets "
                         "([name=]store[@failure_domain], e.g. "
                         "/mnt/peers/n1@rack0,/mnt/peers/n2@rack1): "
                         "after each local commit the sealed generation "
                         "streams to K peers in the background "
                         "(DESIGN.md §11); --restore falls back to the "
                         "peer tier when the local dir is lost")
    ap.add_argument("--replication-factor", type=int, default=2,
                    help="replicas each checkpoint should reach on the "
                         "peer tier (spread across distinct failure "
                         "domains when available)")
    ap.add_argument("--failure-domain", default=None,
                    help="this node's failure domain; peer placement "
                         "avoids it whenever another usable domain "
                         "exists")
    ap.add_argument("--hydrate-readers", type=int, default=4,
                    help="concurrent ranged-GET readers for remote/peer "
                         "hydration — missing bytes are byte-striped "
                         "this wide when the store supports ranged "
                         "reads (DESIGN.md §12)")
    ap.add_argument("--serve-cache-mb", type=int, default=0,
                    help="serving read-cache budget in MiB (0 = off): "
                         "hydration and per-tensor remote reads go "
                         "through a digest-keyed LRU block cache under "
                         "<ckpt-dir>/.serve-cache (DESIGN.md §12)")
    ap.add_argument("--restore", action="store_true")
    ap.add_argument("--restore-tier", default="local",
                    choices=["local", "peer", "remote"],
                    help="force --restore to hydrate from the peer tier "
                         "or the object store (remote) instead of local "
                         "NVMe")
    ap.add_argument("--restore-readers", default="auto",
                    help="parallel-restore reader workers: 'auto' sizes "
                         "to the saved shard count, an integer forces "
                         "that many, 'none' keeps the legacy "
                         "single-reader load")
    args = ap.parse_args()
    restore_readers = (None if args.restore_readers == "none"
                       else args.restore_readers if
                       args.restore_readers == "auto"
                       else int(args.restore_readers))

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = make_reduced(cfg)

    ckpt = None
    # an explicit --backend wins over --ckpt-mode, including "none"
    if args.ckpt_dir and (args.backend or args.ckpt_mode != "none"):
        ckpt = CheckpointPolicy(
            directory=args.ckpt_dir, every=args.every, mode=args.ckpt_mode,
            pipeline=args.pipeline, backend=args.backend,
            volumes=(args.volumes.split(",") if args.volumes else None),
            restore_readers=restore_readers,
            upload=args.upload_store,
            replicate_peers=(args.peers.split(",") if args.peers
                             else None),
            replication_factor=args.replication_factor,
            failure_domain=args.failure_domain,
            keyframe_every=args.keyframe_every,
            hydrate_readers=args.hydrate_readers,
            serve_cache_mb=args.serve_cache_mb,
            fp=FastPersistConfig(
                strategy=args.writers,
                topology=Topology(dp_degree=args.dp, ranks_per_node=4),
                arena=args.arena,
                snapshot_chunk_mb=args.snapshot_chunk_mb,
                device_dirty=args.device_dirty,
                delta_quantize=args.delta_quantize,
                delta_stripe_min_mb=args.delta_stripe_min_mb,
                writer=WriterConfig(backend=args.io_backend,
                                    queue_depth=args.queue_depth)))

    tr = Trainer(TrainerConfig(
        model=cfg, steps=args.steps, global_batch=args.batch,
        seq_len=args.seq, gas=args.gas, opt=AdamConfig(lr=args.lr),
        checkpoint=ckpt))

    start = 0
    if args.restore and ckpt:
        # restores from any backend's COMMIT-marked checkpoints (legacy
        # pre-engine directories need the old classes — DESIGN.md §4)
        start = tr.restore(tier=args.restore_tier)
        print(f"restored from step {start}")
    t0 = time.perf_counter()
    state, metrics = tr.run(start_step=start)     # ends on the device
    mean_iter = (time.perf_counter() - t0) / max(args.steps - start, 1)
    print(f"done: loss={float(metrics.get('loss', float('nan'))):.4f} "
          f"mean_iter={mean_iter*1e3:.1f}ms "
          f"ckpt_stall={tr.ckpt_stall*1e3:.1f}ms")


if __name__ == "__main__":
    main()
