"""Expert-parallel MoE via shard_map (§Perf iteration 5 for the MoE pair).

The pure-GSPMD sorted dispatch was REFUTED (EXPERIMENTS §Perf/2 it-1):
global gathers over data-sharded tokens degenerate into all-gathers.
This module expresses the same sort-based dispatch with EXPLICIT
per-shard semantics:

  * activations are replicated over the ``model`` axis (standard
    Megatron TP residual stream) and sharded over ``data`` — so every
    model shard already holds the tokens it needs: dispatch gathers are
    LOCAL, no collective;
  * expert weights are sharded over ``model`` (E_loc = E / |model|);
    each shard runs only its experts and contributes zeros for tokens
    routed elsewhere;
  * one ``psum`` over ``model`` combines expert outputs — the same
    collective volume as a dense TP MLP, replacing the all-gather storm.

Inside the shard_map block the code mirrors ``layers.moe_apply_sorted``
with a local-expert mask; correctness is tested against the einsum
baseline on a forced-8-device host (tests/test_moe_ep.py).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig


def _local_moe(p, xt, cfg: ModelConfig, *, model_axis, n_model,
               capacity_factor):
    """Per-shard body. xt (T_loc, D) local tokens; p holds LOCAL expert
    slices (E_loc, D, F) and the replicated router."""
    T, D = xt.shape
    E, K = cfg.moe.n_experts, cfg.moe.top_k
    E_loc = E // n_model
    C = max(int(T * K / E * capacity_factor), 1)

    logits = xt.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                # (T, E)
    gate_v, gate_i = lax.top_k(probs, K)
    gate_v = gate_v / jnp.clip(gate_v.sum(-1, keepdims=True), 1e-9)

    e0 = lax.axis_index(model_axis) * E_loc
    TK = T * K
    flat_e = gate_i.reshape(TK)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.bincount(flat_e, length=E)
    starts = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(TK) - starts[sorted_e]
    local = (sorted_e >= e0) & (sorted_e < e0 + E_loc)
    keep = local & (pos_in_e < C)
    slot = (sorted_e - e0) * C + jnp.clip(pos_in_e, 0, C - 1)

    dest = jnp.where(keep, slot, E_loc * C)
    src_tok = jnp.full((E_loc * C,), T, jnp.int32)
    src_tok = src_tok.at[dest].set((order // K).astype(jnp.int32),
                                   mode="drop")
    xt_pad = jnp.concatenate([xt, jnp.zeros((1, D), xt.dtype)], 0)
    ex_in = xt_pad[src_tok].reshape(E_loc, C, D)           # LOCAL gather

    h = jnp.einsum("ecd,edf->ecf", ex_in, p["wi"].astype(xt.dtype))
    g = jnp.einsum("ecd,edf->ecf", ex_in, p["wg"].astype(xt.dtype))
    ex_out = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h,
                        p["wo"].astype(xt.dtype))

    slot_tk = jnp.full((TK,), E_loc * C, jnp.int32)
    slot_tk = slot_tk.at[order].set(jnp.where(keep, slot, E_loc * C))
    out_pad = jnp.concatenate(
        [ex_out.reshape(E_loc * C, D), jnp.zeros((1, D), xt.dtype)], 0)
    picked = out_pad[slot_tk].reshape(T, K, D)
    partial_out = jnp.einsum("tk,tkd->td", gate_v.astype(xt.dtype), picked)
    # combine across expert shards — the ONLY collective in the layer
    out = lax.psum(partial_out, model_axis)

    onehot = jax.nn.one_hot(gate_i, E, dtype=jnp.float32)
    me = probs.mean(axis=0)
    ce = onehot.sum(1).mean(axis=0)
    aux = cfg.moe.aux_loss_coef * E * jnp.sum(me * ce)
    return out, aux


def make_shard_map_moe(mesh, *, model_axis="model"):
    """Returns moe_kernel(p, x, cfg) -> (out, aux) for use inside a model
    running under ``mesh`` (Auto axes: ``repro.launch.mesh.make_mesh``).
    Token batch must be sharded over the data axes; expert weights over
    ``model``."""
    from jax.sharding import AxisType
    if any(t != AxisType.Auto for t in mesh.axis_types):
        # outside the shard_map the model relies on GSPMD propagation;
        # under Explicit axes its gathers are refused
        raise ValueError(
            f"expert parallelism needs a mesh with Auto axes, got "
            f"{mesh.axis_types}; build it with repro.launch.mesh.make_mesh")
    data_axes = tuple(a for a in mesh.axis_names if a != model_axis)
    n_model = mesh.shape[model_axis]

    def param_spec(path, leaf):
        name = str(getattr(path[-1], "key", path[-1]))
        if name in ("wi", "wg", "wo") and leaf.ndim == 3:
            return P(model_axis, None, None)
        return P(*([None] * leaf.ndim))

    def moe_kernel(p, x, cfg: ModelConfig, **_):
        p_specs = jax.tree_util.tree_map_with_path(param_spec, p)
        body = partial(_local_moe, cfg=cfg, model_axis=model_axis,
                       n_model=n_model,
                       capacity_factor=cfg.moe.capacity_factor)

        def fn(p_loc, x_loc):
            B, L, D = x_loc.shape
            out, aux = body(p_loc, x_loc.reshape(B * L, D))
            # aux is identical across model shards (replicated tokens);
            # pmean over data makes it a replicated scalar output.
            if data_axes:
                aux = lax.pmean(aux, data_axes)
            return out.reshape(B, L, D), aux

        sm = jax.shard_map(
            fn, mesh=mesh,
            in_specs=(p_specs, P(data_axes, None, None)),
            out_specs=(P(data_axes, None, None), P()),
            check_vma=False)
        out, aux = sm(p, x)
        if cfg.moe.dense_residual:
            from repro.models.layers import mlp
            out = out + mlp(p["dense"], x, gated=cfg.gated_mlp,
                            act=jax.nn.silu)
        return out, aux

    return moe_kernel
