"""Pure-jnp oracles for every Pallas kernel (the correctness contract)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.layers import segsum


def _block_axes(x):
    """Every axis but the leading block index."""
    return tuple(range(1, x.ndim))


def ckpt_pack_ref(x2d, *, out_dtype=jnp.bfloat16, scale=1.0):
    """x2d: one block per leading index (any trailing shape)."""
    xf = x2d.astype(jnp.float32) * scale
    return xf.astype(out_dtype), jnp.max(jnp.abs(xf), axis=_block_axes(xf))


_UINTS = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32, 8: jnp.uint64}


def ckpt_pack_dirty_ref(x2d, prev2d, *, out_dtype=None, scale=1.0):
    """Oracle for ckpt_pack.ckpt_pack_dirty_blocks: pack + per-block
    BITWISE change mask vs the previous packed image (NaN-safe, matching
    the host byte compare in delta.dirty_byte_spans)."""
    out_dtype = x2d.dtype if out_dtype is None else out_dtype
    xf = x2d.astype(jnp.float32) * scale
    if jnp.dtype(out_dtype) == x2d.dtype and float(scale) == 1.0:
        y = x2d
    else:
        y = xf.astype(out_dtype)
    ubits = _UINTS[jnp.dtype(out_dtype).itemsize]
    yb = jax.lax.bitcast_convert_type(y, ubits)
    pb = jax.lax.bitcast_convert_type(prev2d, ubits)
    mask = jnp.any(yb != pb, axis=_block_axes(yb)).astype(jnp.int32)
    return y, jnp.max(jnp.abs(xf), axis=_block_axes(xf)), mask


def flash_attention_ref(q, k, v, *, causal=True, window=None, cap=None):
    """q (B,H,Lq,hd); k,v (B,KV,Lk,hd) -> (B,H,Lq,hd)."""
    B, H, Lq, hd = q.shape
    KV, Lk = k.shape[1], k.shape[2]
    rep = H // KV
    kk = jnp.repeat(k, rep, axis=1).astype(jnp.float32)
    vv = jnp.repeat(v, rep, axis=1).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kk)
    s = s / math.sqrt(hd)
    if cap is not None:
        s = cap * jnp.tanh(s / cap)
    qpos = jnp.arange(Lq)[:, None]
    kpos = jnp.arange(Lk)[None, :]
    mask = jnp.ones((Lq, Lk), bool)
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= qpos - kpos < window
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vv).astype(q.dtype)


def ssd_intra_chunk_ref(xc, dAc, Bc, Cc):
    """Matches kernels.ssd_scan.ssd_intra_chunk (fp32 out)."""
    xc = xc.astype(jnp.float32)
    dAc = dAc.astype(jnp.float32)
    Bc = Bc.astype(jnp.float32)
    Cc = Cc.astype(jnp.float32)
    L = jnp.exp(segsum(dAc.transpose(0, 1, 3, 2)))       # (b,nc,h,cl,cl)
    scores = jnp.einsum("bclhn,bcshn->bchls", Cc, Bc)
    return jnp.einsum("bchls,bchls,bcshp->bclhp", scores, L, xc)
