"""End-to-end system behaviour: the paper's core promise — train with
per-iteration FastPersist checkpoints, kill at an arbitrary iteration,
restore, and continue IDENTICALLY to an uninterrupted run."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.checkpointer import FastPersistConfig
from repro.core.partition import Topology
from repro.train.trainer import CheckpointPolicy, Trainer, TrainerConfig


def _tc(tmpdir, model_cfg, steps, mode="fastpersist", pipeline=True,
        every=1):
    return TrainerConfig(
        model=model_cfg, steps=steps, global_batch=4, seq_len=32,
        log_every=1000,
        checkpoint=CheckpointPolicy(
            directory=str(tmpdir), every=every, mode=mode,
            pipeline=pipeline,
            fp=FastPersistConfig(
                strategy="replica",
                topology=Topology(dp_degree=2, ranks_per_node=2))))


@pytest.mark.parametrize("arch", ["stablelm_1_6b", "mamba2_370m"])
def test_interrupt_restore_continue_identical(tmp_path, arch):
    cfg = reduced(get_config(arch))

    # uninterrupted 8-step run
    t_full = Trainer(_tc(tmp_path / "full", cfg, 8))
    state_full, m_full = t_full.run()

    # interrupted run: 5 steps, then a NEW trainer restores and continues
    t_a = Trainer(_tc(tmp_path / "int", cfg, 5))
    t_a.run()
    t_b = Trainer(_tc(tmp_path / "int", cfg, 8))
    start = t_b.restore()
    assert start == 5
    state_res, m_res = t_b.run(start_step=start)

    assert float(m_full["loss"]) == pytest.approx(float(m_res["loss"]),
                                                  rel=1e-5)
    for a, b in zip(jax.tree.leaves(state_full.params),
                    jax.tree.leaves(state_res.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-5)


def test_per_iteration_checkpointing_writes_every_step(tmp_path):
    cfg = reduced(get_config("qwen1_5_4b"))
    t = Trainer(_tc(tmp_path, cfg, 4))
    t.run()
    assert t._ckpt.latest_step() == 4
    for s in range(1, 5):
        loaded, mf = t._ckpt.load(s, like=t.state)
        assert mf.extras["step"] == s


def test_baseline_mode_also_recovers(tmp_path):
    cfg = reduced(get_config("stablelm_1_6b"))
    t = Trainer(_tc(tmp_path, cfg, 3, mode="baseline", pipeline=False))
    t.run()
    loaded, _ = t._ckpt.load(3, like=t.state)
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(t.state)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_unpipelined_fastpersist(tmp_path):
    cfg = reduced(get_config("stablelm_1_6b"))
    t = Trainer(_tc(tmp_path, cfg, 3, pipeline=False))
    state, m = t.run()
    assert t._ckpt.latest_step() == 3


def test_moe_trainer_with_checkpointing(tmp_path):
    cfg = reduced(get_config("qwen3_moe_235b"))
    t = Trainer(_tc(tmp_path, cfg, 3))
    state, m = t.run()
    assert bool(jnp.isfinite(m["loss"]))
    t2 = Trainer(_tc(tmp_path, cfg, 3))
    assert t2.restore() == 3


def test_restore_holds_one_copy_of_the_state(tmp_path, monkeypatch):
    """Resume never materialises a throwaway initial state: the pytree
    structure comes from an abstract trace (model.init and
    init_train_state only ever see tracers, never a concrete key), and
    the restored state is bit-exact."""
    import repro.train.trainer as trainer_mod
    cfg = reduced(get_config("stablelm_1_6b"))
    t_a = Trainer(_tc(tmp_path, cfg, 3))
    t_a.run()

    t_b = Trainer(_tc(tmp_path, cfg, 3))
    ran = []
    real_init, real_its = t_b.model.init, trainer_mod.init_train_state

    def spy_init(rng):
        ran.append(("model.init", not isinstance(rng, jax.core.Tracer)))
        return real_init(rng)

    def spy_its(model, rng):
        ran.append(("init_train_state",
                    not isinstance(rng, jax.core.Tracer)))
        return real_its(model, rng)

    t_b.model = dataclasses.replace(t_b.model, init=spy_init)
    monkeypatch.setattr(trainer_mod, "init_train_state", spy_its)
    assert t_b.restore() == 3
    assert ran and not any(concrete for _, concrete in ran), ran
    for a, b in zip(jax.tree.leaves(t_a.state), jax.tree.leaves(t_b.state)):
        assert isinstance(b, jax.Array)
        np.testing.assert_array_equal(np.atleast_1d(a).view(np.uint8),
                                      np.atleast_1d(b).view(np.uint8))
