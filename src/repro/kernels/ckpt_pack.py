"""ckpt_pack — fused checkpoint-serialization kernel (TPU Pallas).

The paper's serialization step (§2.1.3) flattens/casts every tensor into
the byte stream the writers consume. On TPU we fuse, per VMEM-sized
block: (i) cast to the checkpoint dtype (bf16), (ii) optional scale, and
(iii) a per-block abs-max reduction — used downstream for integrity
checks and for the Check-N-Run-style quantized-checkpoint extension.
One HBM read, one HBM write, no intermediate f32 copy.

Layout: a tensor is flattened, zero-padded and viewed as ``rows`` of
128 lanes (:func:`to_rows`). One amax/mask block is ``block``
consecutive elements, i.e. ``block // 128`` consecutive rows, so a
4 KiB block is exactly one (8,128) f32 tile or one (16,128) bf16 tile.
Each grid step moves ``blocks_per_step`` whole blocks (a multiple of
128), which keeps every VMEM tile aligned to the TPU's (8,128) f32 /
(16,128) 16-bit tiling. The per-block outputs come out lane-dense: each
step writes one (1, blocks_per_step) row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
DEFAULT_BLOCK = 8 * LANES          # 1024 elements: one (8,128) f32 tile
#: input bytes each grid step aims to move (whole blocks, >= 128 of them)
STEP_BYTES = 1 << 20


def check_block(block: int):
    """A block must be whole (8,128) f32 tiles: a multiple of 1024."""
    if block <= 0 or block % DEFAULT_BLOCK:
        raise ValueError(f"block must be a positive multiple of "
                         f"{DEFAULT_BLOCK} elements, got {block}")


def blocks_per_step(n_blocks: int, block: int, itemsize: int) -> int:
    """Blocks one grid step moves: a multiple of 128 (the lane width of
    the per-block outputs) near :data:`STEP_BYTES` of input, never more
    than the tensor needs."""
    per = LANES * max(1, STEP_BYTES // (LANES * block * itemsize))
    return min(per, LANES * -(-n_blocks // LANES))


def padded_blocks(n: int, block: int, itemsize: int) -> int:
    """Block count of an n-element tensor's padded image: whole grid
    steps, so no grid step reads past the end."""
    n_blocks = max(1, -(-n // block))
    step = blocks_per_step(n_blocks, block, itemsize)
    return step * -(-n_blocks // step)


def to_rows(x, block: int):
    """Flatten + zero-pad ``x`` to its padded image, as (rows, 128) in
    x's dtype and bits (zero padding on both sides of a compare keeps
    pad blocks clean)."""
    check_block(block)
    flat = x.reshape(-1)
    total = padded_blocks(flat.shape[0], block, x.dtype.itemsize) * block
    if total != flat.shape[0]:
        flat = jnp.pad(flat, (0, total - flat.shape[0]))
    return flat.reshape(-1, LANES)


def _identity_pack(dtype, out_dtype, scale):
    # scale==1 and matching dtype must be bit-preserving: the packed
    # image lands verbatim in the checkpoint stream, and a f32
    # round-trip could canonicalize NaN payloads
    return jnp.dtype(out_dtype) == jnp.dtype(dtype) and float(scale) == 1.0


def _per_block(v, n_blocks):
    """(n_blocks * R, 128) 32-bit -> (1, n_blocks) per-block max.
    Rows reduce first (VPU maxima across whole vregs), then one
    (n_blocks, 128) -> (128, n_blocks) transpose puts blocks on lanes
    and a sublane reduction finishes each block."""
    partial = jnp.max(v.reshape(n_blocks, -1, LANES), axis=1)
    return jnp.max(partial.T, axis=0, keepdims=True)


def _words(v):
    """Bits of ``v`` as 32-bit words (16-bit pairs pack into one word;
    only whole-block equality is read from the result, so the packing
    order does not matter)."""
    if v.dtype.itemsize == 4:
        return jax.lax.bitcast_convert_type(v, jnp.uint32)
    return pltpu.bitcast(v, jnp.uint32)


def _kernel(x_ref, y_ref, amax_ref, *, out_dtype, scale, bpg):
    xf = x_ref[...].astype(jnp.float32) * scale
    y_ref[...] = xf.astype(out_dtype)
    amax_ref[...] = _per_block(jnp.abs(xf), bpg)


def _dirty_kernel(x_ref, prev_ref, y_ref, amax_ref, mask_ref, *,
                  out_dtype, scale, bpg):
    x = x_ref[...]
    xf = x.astype(jnp.float32) * scale
    y = x if _identity_pack(x.dtype, out_dtype, scale) \
        else xf.astype(out_dtype)
    y_ref[...] = y
    amax_ref[...] = _per_block(jnp.abs(xf), bpg)
    # bitwise (not value) compare in the packed domain: NaN != NaN under
    # float compare, but the host fallback (delta.dirty_byte_spans)
    # compares bytes — comparing the raw words keeps the two paths
    # equivalent
    diff = (_words(y) != _words(prev_ref[...])).astype(jnp.int32)
    mask_ref[...] = _per_block(diff, bpg)


def _specs(rows, block, itemsize):
    """(row BlockSpec, per-block BlockSpec, n_steps, bpg)."""
    n_blocks = rows * LANES // block
    bpg = blocks_per_step(n_blocks, block, itemsize)
    if n_blocks % bpg:
        raise ValueError(f"{n_blocks} blocks are not whole grid steps of "
                         f"{bpg}; pad with to_rows()")
    n_steps = n_blocks // bpg
    row_spec = pl.BlockSpec((bpg * block // LANES, LANES), lambda i: (i, 0))
    blk_spec = pl.BlockSpec((None, 1, bpg), lambda i: (i, 0, 0))
    return row_spec, blk_spec, n_steps, bpg


def ckpt_pack_blocks(x_rows, *, block=DEFAULT_BLOCK, out_dtype=jnp.bfloat16,
                     scale=1.0, interpret=False):
    """x_rows (rows, 128) from :func:`to_rows` -> (packed (rows, 128)
    out_dtype, amax (n_blocks,) f32) over the padded image's blocks."""
    check_block(block)
    rows = x_rows.shape[0]
    row_spec, blk_spec, n_steps, bpg = _specs(
        rows, block, x_rows.dtype.itemsize)
    kernel = functools.partial(_kernel, out_dtype=out_dtype,
                               scale=float(scale), bpg=bpg)
    packed, amax = pl.pallas_call(
        kernel,
        grid=(n_steps,),
        in_specs=[row_spec],
        out_specs=[row_spec, blk_spec],
        out_shape=[jax.ShapeDtypeStruct((rows, LANES), out_dtype),
                   jax.ShapeDtypeStruct((n_steps, 1, bpg), jnp.float32)],
        interpret=interpret,
        name="ckpt_pack",
    )(x_rows)
    return packed, amax.reshape(-1)


def ckpt_pack_dirty_blocks(x_rows, prev_rows, *, block=DEFAULT_BLOCK,
                           out_dtype=jnp.bfloat16, scale=1.0,
                           interpret=False):
    """Pack + per-block change mask against a device-resident image.

    x_rows (rows, 128) from :func:`to_rows`; prev_rows (rows, 128) in
    ``out_dtype`` — the packed image of the previous snapshot, kept
    resident on device. Returns (packed (rows, 128) out_dtype, amax
    (n_blocks,) f32, mask (n_blocks,) int32) over the padded image's
    blocks, with mask[i] = 1 iff block i's packed bytes differ from
    prev_rows'. The snapshot path gathers only mask==1 blocks across
    PCIe (Check-N-Run's incremental-bandwidth win)."""
    check_block(block)
    if prev_rows.shape != x_rows.shape:
        raise ValueError(f"prev image shape {prev_rows.shape} != "
                         f"{x_rows.shape}")
    rows = x_rows.shape[0]
    row_spec, blk_spec, n_steps, bpg = _specs(
        rows, block, x_rows.dtype.itemsize)
    kernel = functools.partial(_dirty_kernel, out_dtype=out_dtype,
                               scale=float(scale), bpg=bpg)
    packed, amax, mask = pl.pallas_call(
        kernel,
        grid=(n_steps,),
        in_specs=[row_spec, row_spec],
        out_specs=[row_spec, blk_spec, blk_spec],
        out_shape=[jax.ShapeDtypeStruct((rows, LANES), out_dtype),
                   jax.ShapeDtypeStruct((n_steps, 1, bpg), jnp.float32),
                   jax.ShapeDtypeStruct((n_steps, 1, bpg), jnp.int32)],
        interpret=interpret,
        name="ckpt_pack_dirty",
    )(x_rows, prev_rows)
    return packed, amax.reshape(-1), mask.reshape(-1)
