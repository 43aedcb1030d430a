"""jit'd public wrappers around the Pallas kernels.

Each kernel compiles to Mosaic when the program is lowered for a TPU
and runs in interpret mode when it is lowered for the CPU; any other
platform is refused. The choice is made per lowering (``_pallas``),
not at import, so importing this module starts no backend and a
program compiled for a TPU never interprets.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ckpt_pack as _cp
from repro.kernels import flash_attention as _fa
from repro.kernels import ssd_scan as _ssd


def _pallas(kernel, *args, **kwargs):
    """``kernel(*args, interpret=..., **kwargs)``: compiled on a TPU,
    interpreted on the CPU, an error on any other platform."""
    return jax.lax.platform_dependent(
        *args,
        tpu=functools.partial(kernel, interpret=False, **kwargs),
        cpu=functools.partial(kernel, interpret=True, **kwargs))


def _blocks(rows, block):
    """Free (rows, 128) -> (n_blocks, block // 128, 128) view: one
    leading index per block, tiles unchanged."""
    return rows.reshape(-1, block // _cp.LANES, _cp.LANES)


@functools.partial(jax.jit, static_argnames=("out_dtype", "block", "scale"))
def ckpt_pack(x, *, out_dtype=jnp.bfloat16, scale=1.0,
              block=_cp.DEFAULT_BLOCK):
    """Flatten+cast+amax any-shape tensor into checkpoint blocks.

    Returns (packed flat array of x.size elements, per-block amax of
    the ceil(x.size / block) blocks)."""
    n = x.size
    packed, amax = _pallas(_cp.ckpt_pack_blocks, _cp.to_rows(x, block),
                           block=block, out_dtype=out_dtype, scale=scale)
    return packed.reshape(-1)[:n], amax[:-(-n // block)]


@functools.partial(jax.jit, static_argnames=("block",))
def pack_blocks(x, *, block=_cp.DEFAULT_BLOCK):
    """Device-side layout-pack only: flatten + zero-pad to the padded
    block image (n_blocks, block // 128, 128), keeping x's dtype and
    bits. This is the baseline image ``ckpt_pack_dirty`` compares
    against — building it with the same pad rule guarantees pad blocks
    never read as dirty."""
    return _blocks(_cp.to_rows(x, block), block)


@functools.partial(jax.jit, static_argnames=("out_dtype", "block", "scale"))
def ckpt_pack_dirty(x, prev, *, out_dtype=None, scale=1.0,
                    block=_cp.DEFAULT_BLOCK):
    """Pack + per-block change mask vs a device-resident previous image.

    ``prev`` is the (n_blocks, block // 128, 128) packed image of the
    LAST snapshot (a prior ``packed`` output, or ``pack_blocks`` of the
    old value). Returns (packed image of the same shape, amax
    (n_blocks,), mask (n_blocks,) int32), one entry per block of the
    padded image (blocks past x's end are padding and always clean).
    With out_dtype=None (same dtype, scale 1) the pack is
    bit-preserving, so mask==0 blocks are byte-identical to the previous
    checkpoint stream — the contract the device-dirty snapshot path
    relies on (DESIGN.md §10)."""
    out_dtype = x.dtype if out_dtype is None else out_dtype
    rows = _cp.to_rows(x, block)
    if prev.size != rows.size:
        raise ValueError(f"prev image shape {prev.shape} does not match "
                         f"the {rows.size // block}-block image of {x.shape}")
    packed, amax, mask = _pallas(
        _cp.ckpt_pack_dirty_blocks, rows, prev.reshape(rows.shape),
        block=block, out_dtype=out_dtype, scale=scale)
    return _blocks(packed, block), amax, mask


@functools.partial(jax.jit, static_argnames=("causal", "window", "cap",
                                             "block_q", "block_k"))
def flash_attention(q, k, v, *, causal=True, window=None, cap=None,
                    block_q=128, block_k=128):
    """q (B,H,Lq,hd); k,v (B,KV,Lk,hd)."""
    return _pallas(_fa.flash_attention, q, k, v, causal=causal,
                   window=window, cap=cap, block_q=block_q,
                   block_k=block_k)


@jax.jit
def ssd_intra_chunk(xc, dAc, Bc, Cc):
    return _pallas(_ssd.ssd_intra_chunk, xc, dAc, Bc, Cc)
