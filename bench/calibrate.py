#!/usr/bin/env python3
"""Readings the limits of ``correct`` are set from, at a cell's own
size, on a TPU. Not part of a benchmark run.

    python3 bench/calibrate.py --workload <name> --seeds 1 2 3 ... \
        [--fault none|control|half_batch]

For each seed it drives the cell's train step through its first checked
steps, exactly as a run's set-up does (``Trainer.run``, no checkpoint
engine), reads the same numbers, and compares them with the plain
reference. ``--fault control`` puts the reference computed one precision
below the configuration's (every matmul operand in float8_e4m3fn) in the
program's place; ``--fault half_batch`` trains on the first half of each
batch. Prints one JSON line per seed with the gaps.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import harness as hz  # noqa: E402


def control_step(m: dict, quant):
    """A train step whose loss and gradient come from the reference at
    ``quant``, then the system's own optimizer update."""
    import jax
    import jax.numpy as jnp
    from repro.optim import adam
    from repro.train.steps import TrainState
    from reference import loss_and_grad

    def factory(model, opt_cfg, gas=1):
        def step(state, batch):
            p32 = jax.tree.map(lambda p: p.astype(jnp.float32), state.params)
            with jax.default_matmul_precision("highest"):
                loss, grads = loss_and_grad(m, p32, batch["tokens"],
                                            batch["labels"], quant=quant)
            params, opt = adam.apply(opt_cfg, grads, state.opt)
            return TrainState(params, opt), {"loss": loss, "step": opt.step}
        return step
    return factory


def half_batch_step(real):
    def factory(model, opt_cfg, gas=1):
        step = real(model, opt_cfg, gas)

        def half(state, batch):
            import jax
            return step(state, jax.tree.map(
                lambda x: x[: x.shape[0] // 2], batch))
        return half
    return factory


def plant(fault: str, m: dict):
    """Swap the trainer's step factory for the fault's."""
    import jax.numpy as jnp
    import repro.train.trainer as trainer_mod
    if fault == "control":
        trainer_mod.make_train_step = control_step(m, jnp.float8_e4m3fn)
    elif fault == "half_batch":
        trainer_mod.make_train_step = half_batch_step(
            trainer_mod.make_train_step)


def readings(cell: dict, seed: int) -> dict:
    from reference import train_readings
    conf, traffic = cell["config"], cell["traffic"]
    m, o = conf["model"], conf["optimizer"]
    wseed, dseed = hz.run_seeds(seed)
    tr = hz.trainer(conf, traffic, dseed, None, 1)
    tr.state = hz.make_state(conf, wseed)
    prog = hz.program_readings(tr, m, o, wseed, traffic["check_steps"], 0)
    tr.state = None
    del tr
    ref = train_readings(m, o, wseed, dseed, traffic["batch"],
                         traffic["seq_len"], traffic["check_steps"])
    return {"seed": seed, **hz.train_gaps(prog, ref, conf["skip_leaf_frac"]),
            "prog": prog, "ref": ref}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", default="none",
                    choices=("none", "control", "half_batch"))
    args = ap.parse_args(argv)
    cell = hz.load_cell(args.workload)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    try:
        hz.require_tpu(cell["cell"]["chips"])
    except hz.NoDevice as e:
        sys.exit(f"calibrate: {e}")
    plant(args.fault, cell["config"]["model"])
    for s in args.seeds:
        print(json.dumps({"fault": args.fault, **readings(cell, s)}),
              flush=True)


if __name__ == "__main__":
    main()
