"""The paper's own GPT-3 family: Table 2 checkpoint sizes + trainability."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import (ARCH_IDS, PAPER_TABLE2, get_config,
                           get_paper_config, reduced)
from repro.configs.base import PAPER_ARCH_IDS
from repro.models.registry import build_model, make_batch
from repro.optim.adam import AdamConfig
from repro.train.steps import init_train_state, make_train_step


@pytest.mark.parametrize("key", ["gpt3_0_7b", "gpt3_1_3b", "gpt3_2_7b",
                                 "gpt3_6_7b", "gpt3_13b", "gpt3_1_8b_moe"])
def test_table2_checkpoint_sizes(key):
    """S_C ≈ 14·N reproduces the paper's Table 2 within 15 %."""
    cfg = get_paper_config(key)
    got = cfg.checkpoint_bytes() / 1e9
    want = PAPER_TABLE2[key]["ckpt_gb"]
    assert abs(got - want) / want < 0.15, (key, got, want)


def test_gpt3_reduced_trains():
    cfg = reduced(get_paper_config("gpt3_1_3b"))
    m = build_model(cfg)
    state = init_train_state(m, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(m, AdamConfig(warmup_steps=1)))
    _, metrics = step(state, make_batch(cfg, 2, 32))
    assert bool(jnp.isfinite(metrics["loss"]))


def test_gradient_accumulation_matches_large_batch():
    """§2.1.2: GA over microbatches == one large batch (same grads)."""
    cfg = reduced(get_paper_config("gpt3_0_7b"))
    m = build_model(cfg, dtype=jnp.float32)
    batch = make_batch(cfg, 4, 16)
    s0 = init_train_state(m, jax.random.PRNGKey(0))
    opt = AdamConfig(warmup_steps=1)
    s1, m1 = jax.jit(make_train_step(m, opt, gas=1))(s0, batch)
    s0b = init_train_state(m, jax.random.PRNGKey(0))
    s2, m2 = jax.jit(make_train_step(m, opt, gas=2))(s0b, batch)
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-4)
    # Adam's first-step g/√v̂ normalization amplifies fp32 summation-order
    # noise, so compare updated masters with an update-scale tolerance
    # (lr=3e-4 ⇒ |update| ≤ ~lr·(1+wd)).
    a = jax.tree.leaves(s1.opt.master)[0]
    b = jax.tree.leaves(s2.opt.master)[0]
    import numpy as np
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3)


def test_ce_chunking_equals_full():
    cfg = reduced(get_paper_config("gpt3_0_7b"))
    batch = make_batch(cfg, 2, 32)
    m_full = build_model(cfg, dtype=jnp.float32)
    m_chunk = build_model(cfg, dtype=jnp.float32, ce_chunk=8)
    p = m_full.init(jax.random.PRNGKey(0))
    l1 = float(jax.jit(m_full.loss)(p, batch))
    l2 = float(jax.jit(m_chunk.loss)(p, batch))
    assert l1 == pytest.approx(l2, rel=1e-5)


@pytest.mark.parametrize("arch", [*ARCH_IDS, *PAPER_ARCH_IDS])
def test_get_config_resolves_every_id(arch):
    """The launcher's --arch takes the assigned and the paper's IDs."""
    cfg = get_config(arch)
    assert cfg.n_layers > 0 and cfg.d_model > 0
    assert get_config(arch.replace("_", "-")) == cfg
