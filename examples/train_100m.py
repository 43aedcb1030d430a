"""End-to-end driver: train a ~100M-parameter GPT-3-small-class model for
a few hundred steps with FastPersist checkpointing every iteration
(paper's target workload, scaled to this machine).

    PYTHONPATH=src python examples/train_100m.py [--steps 200]
"""
import argparse
import shutil
import time

from repro.configs.base import ModelConfig
from repro.core.checkpointer import FastPersistConfig
from repro.core.partition import Topology
from repro.optim.adam import AdamConfig
from repro.train.trainer import CheckpointPolicy, Trainer, TrainerConfig

# ~100M params: 12L × 768 (GPT-3 Small geometry, gated MLP off)
GPT3_SMALL = ModelConfig(
    name="gpt3-small-100m", arch_type="dense", n_layers=12, d_model=768,
    n_heads=12, n_kv_heads=12, d_ff=3072, vocab_size=50257,
    gated_mlp=False, tie_embeddings=True,
    source="arXiv:2005.14165")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--dir", default="/tmp/fastpersist_100m")
    args = ap.parse_args()
    shutil.rmtree(args.dir, ignore_errors=True)

    print(f"params: {GPT3_SMALL.param_count()/1e6:.0f}M  "
          f"checkpoint: {GPT3_SMALL.checkpoint_bytes()/1e9:.2f} GB")

    tr = Trainer(TrainerConfig(
        model=GPT3_SMALL, steps=args.steps, global_batch=args.batch,
        seq_len=args.seq, opt=AdamConfig(lr=6e-4, warmup_steps=50),
        log_every=20,
        checkpoint=CheckpointPolicy(
            directory=args.dir, every=1, backend="fastpersist-pipelined",
            fp=FastPersistConfig(
                strategy="auto",
                topology=Topology(dp_degree=8, ranks_per_node=4)))))
    warm = min(5, args.steps)           # compiles, first saves
    tr.cfg.steps = warm
    tr.run()
    tr.cfg.steps = args.steps
    stall0 = tr.ckpt_stall
    t0 = time.perf_counter()
    state, metrics = tr.run(start_step=warm)      # ends on the device
    wall = time.perf_counter() - t0
    it = wall / max(args.steps - warm, 1)
    stall = tr.ckpt_stall - stall0
    print(f"\nfinal loss {float(metrics['loss']):.4f}")
    print(f"mean iter time {it*1e3:.0f} ms  "
          f"ckpt stall total {stall*1e3:.0f} ms "
          f"({100*stall/max(wall, 1e-9):.1f}% of train time)")
    # Eq. 1 check for THIS host: B_C needed vs what the disk delivers.
    from repro.core.overlap import IterationModel, required_bandwidth
    fb = it * 0.9
    bc = required_bandwidth(GPT3_SMALL.checkpoint_bytes(),
                            IterationModel(fb / 3, 2 * fb / 3, fb * 0.1))
    print(f"Eq.1: hiding a {GPT3_SMALL.checkpoint_bytes()/1e9:.1f} GB "
          f"ckpt behind {fb*1e3:.0f} ms of compute needs "
          f"{bc/1e9:.1f} GB/s — a single laptop-class disk (~0.6 GB/s) "
          f"stalls; the paper's 8-SSD nodes (24.8 GB/s) do not.")


if __name__ == "__main__":
    main()
