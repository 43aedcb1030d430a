"""Plain reference of the benchmark's models and optimizer.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision, written from the published description and the semantics the
configuration files state. It imports nothing of the system under test
and takes nothing it made: the benchmark makes the weights and the
token batches here, from the seed, and hands copies to both sides.

The model is a pre-norm decoder: RMSNorm, rotary attention with a
causal mask, a SiLU MLP (or a top-1 mixture of gated-SiLU experts with
a capacity of ``capacity_factor * tokens / experts`` per expert, filled
in token order, and a Switch-style balance loss), a final RMSNorm and
logits against the tied embedding; the loss is the mean cross entropy
plus the balance loss. The optimizer is Adam with global-norm clipping,
linear warm-up and decoupled weight decay on an fp32 master copy, whose
bf16 rounding is what the forward pass reads.

A batch is worked through one row at a time (with every layer
rematerialised), so that the reference fits beside nothing else on one
chip at the benchmark's own batch and sequence length. Routing of an
expert layer depends on every token of the batch (capacity is filled in
token order), so it is decided first, layer by layer, over all rows.

``quant`` names the dtype every matmul operand is rounded to before the
float32 product: None for the reference itself, ``float8_e4m3fn`` for
the control that computes one precision below the configuration's
bf16.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


# ------------------------------------------------------------ the model
def param_shapes(m: dict) -> dict:
    """Leaf shapes of the parameter tree, keyed as the system names
    them; layers are stacked on a leading axis."""
    L, d, H, ff, V = (m["n_layers"], m["d_model"], m["n_heads"], m["d_ff"],
                      m["vocab_size"])
    hd = d // H
    attn = {"wq": (L, d, H * hd), "wk": (L, d, H * hd),
            "wv": (L, d, H * hd), "wo": (L, H * hd, d)}
    if m.get("n_experts"):
        E = m["n_experts"]
        mlp = {"router": (L, d, E), "wi": (L, E, d, ff), "wg": (L, E, d, ff),
               "wo": (L, E, ff, d)}
    else:
        mlp = {"wi": (L, d, ff), "wo": (L, ff, d)}
    return {"embed": (V, d),
            "layers": {"ln1": (L, d), "ln2": (L, d), "attn": attn,
                       "mlp": mlp},
            "final_norm": (d,)}


def init_params(m: dict, seed: int):
    """GPT-3 initialisation (arXiv:2005.14165 follows GPT-2): N(0, 0.02)
    for every matrix, output projections scaled by 1/sqrt(2 n_layers),
    norm gains 1. Float32; traced inside one jit by the caller."""
    shapes = param_shapes(m)
    flat, tdef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    key = jax.random.PRNGKey(seed)
    out = []
    for i, (path, shape) in enumerate(flat):
        name = jax.tree_util.keystr(path)
        if "ln" in name or "norm" in name:
            out.append(jnp.ones(shape, F32))
            continue
        std = 0.02
        if name.endswith("['wo']"):
            std /= math.sqrt(2 * m["n_layers"])
        out.append(std * jax.random.normal(jax.random.fold_in(key, i),
                                           shape, F32))
    return jax.tree_util.tree_unflatten(tdef, out)


def token_batch(m: dict, seed: int, index: int, batch: int, seq: int):
    """Row ``index`` of the token stream: uniform ids from
    fold_in(PRNGKey(seed), index), labels shifted by one."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), index)
    k1, _ = jax.random.split(key)
    t = jax.random.randint(k1, (batch, seq + 1), 0, m["vocab_size"],
                           jnp.int32)
    return t[:, :-1], t[:, 1:]


def _q(x, quant):
    return x if quant is None else x.astype(quant).astype(F32)


def _mm(a, b, quant):
    return jnp.matmul(_q(a, quant), _q(b, quant))


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x (S, H, hd): rotate-half pairs (first half, second half)."""
    S, _, hd = x.shape
    half = hd // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=F32) * (math.log(theta) / half))
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attention(m, lp, h, quant):
    S, d = h.shape
    H = m["n_heads"]
    hd = d // H
    q = _rope(_mm(h, lp["wq"], quant).reshape(S, H, hd), m["rope_theta"])
    k = _rope(_mm(h, lp["wk"], quant).reshape(S, H, hd), m["rope_theta"])
    v = _mm(h, lp["wv"], quant).reshape(S, H, hd)
    s = jnp.einsum("qhd,khd->hqk", _q(q, quant), _q(k, quant)) / math.sqrt(hd)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -1e30), -1)
    o = jnp.einsum("hqk,khd->qhd", _q(p, quant), _q(v, quant))
    return _mm(o.reshape(S, d), lp["wo"], quant)


def _experts(lp, h, idx, keep, quant):
    """Each kept token through its own expert; dropped tokens give 0."""
    E = lp["wi"].shape[0]

    def one(acc, e):
        g = _mm(h, lp["wg"][e], quant)
        u = _mm(h, lp["wi"][e], quant)
        y = _mm(jax.nn.silu(g) * u, lp["wo"][e], quant)
        sel = ((idx == e) & keep).astype(F32)[:, None]
        return acc + sel * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(E))
    return out


def _layer(m, x, lp, route, quant):
    """One block on one row. ``route`` = (idx, keep, ce) of this row's
    tokens for an expert layer, None for a dense one."""
    x = x + _attention(m, lp["attn"], _rmsnorm(x, lp["ln1"], m["norm_eps"]),
                       quant)
    h = _rmsnorm(x, lp["ln2"], m["norm_eps"])
    if route is None:
        mp = lp["mlp"]
        return x + _mm(jax.nn.silu(_mm(h, mp["wi"], quant)), mp["wo"],
                       quant), 0.0
    idx, keep, ce = route
    mp = lp["mlp"]
    probs = jax.nn.softmax(_mm(h, mp["router"], quant), -1)
    # balance loss, this row's share: coef * E * sum_e mean(probs) * ce
    aux_part = m["aux_loss_coef"] * probs.shape[-1] * jnp.sum(
        probs.sum(0) * ce)
    return x + _experts(mp, h, idx, keep, quant), aux_part


def _row_loss(m, params, tokens, labels, routes, n_tok, quant):
    """Summed CE of one row over ``n_tok`` plus its balance-loss share
    (every layer rematerialised)."""
    x = params["embed"][tokens]
    aux = 0.0
    layers = params["layers"]
    for i in range(m["n_layers"]):
        lp = jax.tree.map(lambda a: a[i], layers)
        r = None if routes is None else jax.tree.map(lambda a: a[i], routes)
        x, a = jax.checkpoint(partial(_layer, m), static_argnums=(3,))(
            x, lp, r, quant)
        aux = aux + a / n_tok
    x = _rmsnorm(x, params["final_norm"], m["norm_eps"])
    logits = _mm(x, params["embed"].T, quant)
    lse = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
    return jnp.sum(lse - gold) / n_tok + aux


def _route(m, params, tokens, quant):
    """Top-1 expert, kept flag and the routed fraction per layer, over
    all rows: capacity is filled in token order across the batch."""
    B, S = tokens.shape
    E = m["n_experts"]
    T = B * S
    C = max(int(T / E * m["capacity_factor"]), 1)
    x = params["embed"][tokens]
    idxs, keeps, ces = [], [], []
    for i in range(m["n_layers"]):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        x = jax.lax.map(lambda r: r + _attention(
            m, lp["attn"], _rmsnorm(r, lp["ln1"], m["norm_eps"]), quant), x)
        h = _rmsnorm(x, lp["ln2"], m["norm_eps"])
        idx = jnp.argmax(_mm(h, lp["mlp"]["router"], quant), -1)   # (B,S)
        onehot = jax.nn.one_hot(idx.reshape(T), E, dtype=jnp.int32)
        pos = jnp.take_along_axis(jnp.cumsum(onehot, 0) - 1,
                                  idx.reshape(T, 1), 1)[:, 0]
        keep = (pos < C).reshape(B, S)
        ce = onehot.astype(F32).mean(0)
        x = jax.lax.map(lambda a: a[0] + _experts(
            lp["mlp"], a[1], a[2], a[3], quant), (x, h, idx, keep))
        idxs.append(idx)
        keeps.append(keep)
        ces.append(ce)
    return jnp.stack(idxs, 1), jnp.stack(keeps, 1), jnp.stack(ces)


def loss_and_grad(m: dict, params, tokens, labels, quant=None):
    """Mean loss over the batch and its gradient, row by row."""
    B, S = tokens.shape
    n_tok = B * S
    routes = None
    if m.get("n_experts"):
        idx, keep, ce = _route(m, params, tokens, quant)
        routes = (idx, keep, jnp.broadcast_to(ce, (B,) + ce.shape))
    vg = jax.value_and_grad(
        lambda p, t, l, r: _row_loss(m, p, t, l, r, n_tok, quant))

    def body(acc, row):
        loss, g = vg(params, *row)
        return (acc[0] + loss, jax.tree.map(jnp.add, acc[1], g)), None

    zero = (jnp.zeros((), F32), jax.tree.map(jnp.zeros_like, params))
    rows = (tokens, labels, routes)
    (loss, grads), _ = jax.lax.scan(body, zero, rows)
    return loss, grads


# -------------------------------------------------------- the optimizer
def adam_step(o: dict, step: int, grads, master, mom, vel):
    """One step of the configuration's Adam; ``step`` counts from 1.
    Returns (clipped gradient, master, m, v)."""
    leaves = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    scale = jnp.minimum(1.0, o["grad_clip"] / (gnorm + 1e-9))
    lr = o["lr"] * min(step / o["warmup_steps"], 1.0)
    b1c = 1 - o["b1"] ** step
    b2c = 1 - o["b2"] ** step
    g = jax.tree.map(lambda x: x * scale, grads)
    mom = jax.tree.map(lambda a, x: o["b1"] * a + (1 - o["b1"]) * x, mom, g)
    vel = jax.tree.map(lambda a, x: o["b2"] * a + (1 - o["b2"]) * x * x,
                       vel, g)
    master = jax.tree.map(
        lambda w, a, b: w - lr * ((a / b1c) / (jnp.sqrt(b / b2c) + o["eps"])
                                  + o["weight_decay"] * w), master, mom, vel)
    return g, master, mom, vel


def leaf_norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))
            for x in jax.tree.leaves(tree)]


def train_readings(m: dict, o: dict, seed: int, data_seed: int, batch: int,
                   seq: int, steps: int = 3, quant=None) -> dict:
    """What the comparison reads from ``steps`` steps from the
    benchmark's initial weights: the loss of each step, the per-leaf
    norms of the first clipped gradient, and the per-leaf norms of the
    master's change over all steps. Every matmul at ``highest``."""
    with jax.default_matmul_precision("highest"):
        init = jax.jit(lambda: jax.tree.map(
            lambda p: p.astype(jnp.bfloat16).astype(F32),
            init_params(m, seed)))()
        master = init
        mom = jax.tree.map(jnp.zeros_like, init)
        vel = jax.tree.map(jnp.zeros_like, init)
        lg = jax.jit(partial(loss_and_grad, m, quant=quant))
        upd = jax.jit(partial(adam_step, o), static_argnums=0)
        losses, g_first = [], None
        for s in range(1, steps + 1):
            tokens, labels = token_batch(m, data_seed, s - 1, batch, seq)
            params = jax.tree.map(
                lambda p: p.astype(jnp.bfloat16).astype(F32), master)
            loss, grads = lg(params, tokens, labels)
            del params
            g, master, mom, vel = upd(s, grads, master, mom, vel)
            del grads
            losses.append(float(loss))
            if s == 1:
                g_first = [float(x) for x in leaf_norms(g)]
            del g
        delta = jax.jit(lambda a, b: leaf_norms(jax.tree.map(
            jnp.subtract, a, b)))(master, init)
        return {"loss": losses, "grad": g_first,
                "change": [float(x) for x in delta]}


def worst_leaf_gap(got, want, skip=()):
    """Largest |got - want| over leaves, each against the larger of its
    own reference norm and the median leaf's. Leaves in ``skip`` are
    left out."""
    want = np.asarray(want, np.float64)
    got = np.asarray(got, np.float64)
    keep = [i for i in range(len(want)) if i not in set(skip)]
    med = float(np.median(want[keep]))
    return max(abs(got[i] - want[i]) / max(want[i], med, 1e-30)
               for i in keep)
