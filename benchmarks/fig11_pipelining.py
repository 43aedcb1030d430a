"""Paper Fig. 11: pipelined checkpointing — REAL training (reduced
GPT-3-class model on CPU) with checkpointing every iteration:
  (a) GAS sweep: slowdown vs no-checkpoint baseline, with/without pipeline
  (b) per-model overhead with pipelining
Training is real JAX, checkpoints are real disk writes."""
import os
import shutil
import time

from benchmarks.common import bench_dir, cleanup, emit
from repro.configs.base import ModelConfig
from repro.core.checkpointer import FastPersistConfig
from repro.core.partition import Topology
from repro.train.trainer import CheckpointPolicy, Trainer, TrainerConfig

TINY = ModelConfig(
    name="gpt3-tiny", arch_type="dense", n_layers=4, d_model=256,
    n_heads=8, n_kv_heads=8, d_ff=1024, vocab_size=8192, gated_mlp=False,
    source="bench")


def _run(steps, gas, backend, d):
    """``backend`` is a CheckpointEngine registry key, or "none"."""
    shutil.rmtree(d, ignore_errors=True)
    pol = None
    if backend != "none":
        pol = CheckpointPolicy(
            directory=d, every=1, backend=backend,
            fp=FastPersistConfig(strategy="replica",
                                 topology=Topology(dp_degree=4,
                                                   ranks_per_node=4)))
    tr = Trainer(TrainerConfig(model=TINY, steps=2,
                               global_batch=4 * gas, seq_len=128, gas=gas,
                               log_every=10**9, checkpoint=pol))
    tr.run()                        # compiles: left out of the time
    tr.cfg.steps = steps
    t0 = time.perf_counter()
    tr.run(start_step=2)            # ends on the device
    return (time.perf_counter() - t0) / (steps - 2)


def run(quick=True):
    steps = 8 if quick else 16
    out = {}
    gas_list = [1, 4, 16] if quick else [1, 2, 4, 8, 16, 64]
    for gas in gas_list:
        d = os.path.join(bench_dir(), "f11")
        t_none = _run(steps, gas, "none", d)
        t_fp = _run(steps, gas, "fastpersist", d)
        t_pipe = _run(steps, gas, "fastpersist-pipelined", d)
        t_base = _run(steps, gas, "baseline", d)
        shutil.rmtree(d, ignore_errors=True)
        slow_fp = t_fp / t_none - 1
        slow_pipe = t_pipe / t_none - 1
        slow_base = t_base / t_none - 1
        out[gas] = (slow_base, slow_fp, slow_pipe)
        emit(f"fig11a/gas{gas}_baseline", t_base,
             f"{100*slow_base:.1f}%_slowdown")
        emit(f"fig11a/gas{gas}_fastpersist", t_fp,
             f"{100*slow_fp:.1f}%_slowdown")
        emit(f"fig11a/gas{gas}_pipelined", t_pipe,
             f"{100*slow_pipe:.1f}%_slowdown")
    return out


if __name__ == "__main__":
    run()
    cleanup()
