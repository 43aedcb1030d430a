"""The comparison that decides ``correct``, driven through a whole job at
a small size on the CPU (the look for a chip is skipped): a sound run
passes, and each fault planted under the timed path, and the control,
comes out not correct under the committed limits."""
import os
import sys
import time

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import calibrate  # noqa: E402
import harness as hz  # noqa: E402
import jobs  # noqa: E402

CELLS = ["gpt3-0.7b.steady", "gpt3-1.8b-moe.resume"]


def small(name):
    cell = hz.load_cell(name)
    m = cell["config"]["model"]
    m.update(n_layers=1, d_model=64, n_heads=4, d_ff=128, vocab_size=256)
    if m.get("n_experts"):
        m["n_experts"] = 4
    cell["traffic"].update(batch=4, seq_len=32)
    if cell["traffic"]["kind"] == "steady":
        cell["traffic"]["save_every"] = 5
    return cell


def verdict(name, monkeypatch, fault=None):
    import jax
    cell = small(name)
    monkeypatch.setattr(hz, "CKPT_DIR", str(cell_dir(monkeypatch)))
    if fault is not None:
        fault(cell, monkeypatch)
    run = jobs.JOBS[cell["traffic"]["kind"]](
        cell, 3000000007, 0.5, None, jax.devices(), time.perf_counter())
    ok, checks = hz.verdict(run.readings, {
        k: cell["config"]["limits"][k] for k in run.readings})
    return ok and run.failed == 0, checks


def cell_dir(monkeypatch):
    import tempfile
    d = tempfile.mkdtemp()
    return os.path.join(d, "ckpt")


# ------------------------------------------------------------- faults
def unchanged_state(cell, mp):
    """A step that returns its state unchanged (the loss still right)."""
    import repro.train.trainer as trainer_mod
    real = trainer_mod.make_train_step

    def factory(model, opt_cfg, gas=1):
        step = real(model, opt_cfg, gas)

        def same(state, batch):
            import jax
            _, metrics = step(jax.tree.map(lambda x: x + 0, state), batch)
            return state, metrics
        return same
    mp.setattr(trainer_mod, "make_train_step", factory)


def half_batch(cell, mp):
    import repro.train.trainer as trainer_mod
    mp.setattr(trainer_mod, "make_train_step",
               calibrate.half_batch_step(trainer_mod.make_train_step))


def control(cell, mp):
    """The reference in the program's place, one precision below."""
    import jax.numpy as jnp
    import repro.train.trainer as trainer_mod
    mp.setattr(trainer_mod, "make_train_step",
               calibrate.control_step(cell["config"]["model"],
                                      jnp.float8_e4m3fn))


def altered_answer(cell, mp):
    """One byte altered where the answer is produced: in what a load
    hands back (the read-back check of a save, the state a resume
    restores)."""
    from repro.core.engine import CheckpointEngine
    real = CheckpointEngine.load

    def load(self, *a, **kw):
        import jax
        tree, manifest = real(self, *a, **kw)
        leaves, tdef = jax.tree.flatten(tree)
        x = np.array(leaves[1])
        x.view(np.uint8).reshape(-1)[7] ^= 0x10
        leaves[1] = x
        return jax.tree.unflatten(tdef, leaves), manifest
    mp.setattr(CheckpointEngine, "load", load)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name, monkeypatch):
    ok, checks = verdict(name, monkeypatch)
    assert ok, checks


@pytest.mark.parametrize("fault", [unchanged_state, half_batch, control,
                                   altered_answer],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("name", CELLS)
def test_fault_is_not_correct(name, fault, monkeypatch):
    ok, checks = verdict(name, monkeypatch, fault)
    assert not ok, checks
