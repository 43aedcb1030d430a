"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret
mode (CPU), per the assignment's kernel-validation requirement."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def _rand(shape, dtype):
    x = RNG.standard_normal(shape).astype("float32")
    return jnp.asarray(x, dtype=dtype)


# ------------------------------------------------------------ ckpt_pack
@pytest.mark.parametrize("shape", [(8,), (1000,), (37, 1000), (5, 7, 64),
                                   (8192,), (3, 8192)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ckpt_pack_sweep(shape, dtype):
    x = _rand(shape, dtype)
    packed, amax = ops.ckpt_pack(x, block=1024)
    n = x.size
    flat = x.reshape(-1)
    pad = (-n) % 1024
    x2d = jnp.pad(flat, (0, pad)).reshape(-1, 1024)
    pref, aref = ref.ckpt_pack_ref(x2d)
    np.testing.assert_allclose(np.asarray(packed, np.float32),
                               np.asarray(pref.reshape(-1)[:n], np.float32))
    np.testing.assert_allclose(np.asarray(amax), np.asarray(aref),
                               rtol=1e-6)


def test_ckpt_pack_scale():
    x = _rand((2048,), jnp.float32)
    packed, amax = ops.ckpt_pack(x, scale=0.5, block=1024)
    np.testing.assert_allclose(np.asarray(packed, np.float32),
                               np.asarray((x * 0.5).astype(jnp.bfloat16),
                                          np.float32))


# ------------------------------------------------- ckpt_pack dirty masks
@pytest.mark.parametrize("shape", [(8,), (1000,), (37, 1000), (8192,),
                                   (1023,), (1025,)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ckpt_pack_dirty_matches_ref(shape, dtype):
    """Kernel mask/pack == pure-jnp reference, incl. non-block-multiple
    shapes (pad blocks) and identity (same-dtype, bit-preserving) packs."""
    block = 1024
    old = _rand(shape, dtype)
    new = np.asarray(old, np.float32).copy()
    idx = RNG.choice(new.size, size=max(1, new.size // 7), replace=False)
    new.reshape(-1)[idx] += 1.0
    new = jnp.asarray(new, dtype=dtype)
    prev2d = ops.pack_blocks(old, block=block)
    packed, amax, mask = ops.ckpt_pack_dirty(new, prev2d, block=block)
    x2d = ops.pack_blocks(new, block=block)
    pref, aref, mref = ref.ckpt_pack_dirty_ref(x2d, prev2d)
    np.testing.assert_array_equal(np.asarray(mask), np.asarray(mref))
    np.testing.assert_array_equal(
        np.asarray(packed).view(np.uint8), np.asarray(pref).view(np.uint8))
    np.testing.assert_allclose(np.asarray(amax), np.asarray(aref),
                               rtol=1e-6)


def test_ckpt_pack_dirty_self_clean_and_pad_blocks():
    """Unchanged tensor ⇒ all-clean mask; the zero-pad rule keeps pad
    blocks clean even for non-multiple sizes; all-zero data blocks are
    clean against an all-zero baseline (mask means CHANGED, not
    nonzero)."""
    block = 1024
    x = _rand((3000,), jnp.float32)           # 3 blocks, 72-elem pad
    prev2d = ops.pack_blocks(x, block=block)
    _, _, mask = ops.ckpt_pack_dirty(x, prev2d, block=block)
    assert not np.asarray(mask).any()
    z = jnp.zeros((3000,), jnp.float32)
    _, _, mz = ops.ckpt_pack_dirty(z, ops.pack_blocks(z, block=block),
                                   block=block)
    assert not np.asarray(mz).any()


def test_ckpt_pack_dirty_mask_equals_host_spans():
    """THE device-mask / host-compare equivalence rule (DESIGN.md §10):
    mask_to_spans(kernel mask) == dirty_byte_spans(host byte compare)
    for identity packs, including the clipped tail span."""
    from repro.core.delta import dirty_byte_spans, mask_to_spans
    block = 1024                               # elements
    for n in (4096, 5000, 1023):               # multiple / tail / tiny
        old = np.asarray(_rand((n,), jnp.float32))
        new = old.copy()
        if n > 100:
            new[5] += 1.0
            new[-1] -= 2.0
        bb = block * 4                         # bytes per block
        want = dirty_byte_spans(old.view(np.uint8), new.view(np.uint8),
                                block=bb)
        prev2d = ops.pack_blocks(jnp.asarray(old), block=block)
        _, _, mask = ops.ckpt_pack_dirty(jnp.asarray(new), prev2d,
                                         block=block)
        got = mask_to_spans(np.asarray(mask), bb, old.nbytes)
        assert got == want, (n, got, want)


def test_ckpt_pack_dirty_nan_stable():
    """Bitwise compare: an unchanged NaN payload reads CLEAN (== host
    byte compare), unlike a value compare where NaN != NaN."""
    block = 1024
    x = np.asarray(_rand((2048,), jnp.float32)).copy()
    x[100] = np.nan
    xs = jnp.asarray(x)
    _, _, mask = ops.ckpt_pack_dirty(xs, ops.pack_blocks(xs, block=block),
                                     block=block)
    assert not np.asarray(mask).any()


def test_ckpt_pack_dirty_shape_mismatch():
    # images pad to whole grid steps (128 blocks here), so the two
    # tensors must differ by more than that to get different images
    x = _rand((2048,), jnp.float32)
    prev2d = ops.pack_blocks(_rand((129 * 1024,), jnp.float32), block=1024)
    with pytest.raises(ValueError):
        ops.ckpt_pack_dirty(x, prev2d, block=1024)


# ------------------------------------------------------- flash attention
@pytest.mark.parametrize("B,H,KV,L,hd", [
    (1, 4, 4, 128, 64),       # MHA
    (2, 8, 2, 256, 64),       # GQA 4:1
    (1, 4, 1, 384, 128),      # MQA, non-pow2 length
    (1, 2, 2, 100, 64),       # unaligned length (padding path)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, H, KV, L, hd, dtype):
    q = _rand((B, H, L, hd), dtype)
    k = _rand((B, KV, L, hd), dtype)
    v = _rand((B, KV, L, hd), dtype)
    out = ops.flash_attention(q, k, v, block_q=128, block_k=128)
    want = ref.flash_attention_ref(q, k, v)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)


@pytest.mark.parametrize("kwargs", [
    {"window": 64}, {"cap": 50.0}, {"causal": False},
    {"window": 32, "cap": 30.0},
])
def test_flash_attention_variants(kwargs):
    q = _rand((1, 4, 256, 64), jnp.float32)
    k = _rand((1, 2, 256, 64), jnp.float32)
    v = _rand((1, 2, 256, 64), jnp.float32)
    out = ops.flash_attention(q, k, v, **kwargs)
    want = ref.flash_attention_ref(q, k, v, **kwargs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=3e-5, rtol=3e-5)


def test_flash_attention_cross_lengths():
    q = _rand((1, 4, 128, 64), jnp.float32)
    k = _rand((1, 4, 512, 64), jnp.float32)
    v = _rand((1, 4, 512, 64), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=False)
    want = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# -------------------------------------------------------------- ssd_scan
@pytest.mark.parametrize("b,nc,cl,h,p,n", [
    (1, 2, 64, 2, 32, 16),
    (2, 4, 128, 4, 64, 32),
    (1, 1, 256, 8, 64, 64),
])
def test_ssd_intra_chunk_sweep(b, nc, cl, h, p, n):
    xc = _rand((b, nc, cl, h, p), jnp.float32)
    dAc = -jnp.abs(_rand((b, nc, cl, h), jnp.float32)) * 0.1
    Bc = _rand((b, nc, cl, h, n), jnp.float32)
    Cc = _rand((b, nc, cl, h, n), jnp.float32)
    y = ops.ssd_intra_chunk(xc, dAc, Bc, Cc)
    want = ref.ssd_intra_chunk_ref(xc, dAc, Bc, Cc)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_ssd_kernel_hook_in_model():
    """ssd_chunked(ssd_kernel=pallas) == ssd_chunked(pure jnp)."""
    from repro.models.layers import ssd_chunked
    b, l, h, p, n, chunk = 1, 64, 2, 32, 16, 16
    x = _rand((b, l, h, p), jnp.float32)
    dt = jnp.abs(_rand((b, l, h), jnp.float32)) * 0.1 + 0.01
    A = -jnp.abs(_rand((h,), jnp.float32))
    B_ = _rand((b, l, 1, n), jnp.float32)
    C_ = _rand((b, l, 1, n), jnp.float32)
    D = _rand((h,), jnp.float32)
    y0, s0 = ssd_chunked(x, dt, A, B_, C_, D, chunk)
    y1, s1 = ssd_chunked(x, dt, A, B_, C_, D, chunk,
                         ssd_kernel=lambda *a: ops.ssd_intra_chunk(*a))
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), atol=2e-4,
                               rtol=2e-4)
    np.testing.assert_allclose(np.asarray(s0), np.asarray(s1), atol=2e-4,
                               rtol=2e-4)


def test_ssd_chunked_matches_naive_recurrence():
    """The chunked SSD algorithm == step-by-step recurrence oracle."""
    from repro.models.layers import ssd_chunked
    b, l, h, p, n, chunk = 1, 32, 2, 8, 4, 8
    x = _rand((b, l, h, p), jnp.float32)
    dt = jnp.abs(_rand((b, l, h), jnp.float32)) * 0.1 + 0.01
    A = -jnp.abs(_rand((h,), jnp.float32))
    B_ = _rand((b, l, 1, n), jnp.float32)
    C_ = _rand((b, l, 1, n), jnp.float32)
    D = jnp.zeros((h,))
    y, final = ssd_chunked(x, dt, A, B_, C_, D, chunk)

    state = np.zeros((b, h, p, n), np.float32)
    ys = []
    xn, dtn = np.asarray(x), np.asarray(dt)
    Bn, Cn, An = np.asarray(B_), np.asarray(C_), np.asarray(A)
    for t in range(l):
        dA = np.exp(dtn[:, t] * An[None])                  # (b,h)
        xb = xn[:, t] * dtn[:, t][..., None]               # (b,h,p)
        state = state * dA[..., None, None] + \
            np.einsum("bhp,bn->bhpn", xb, Bn[:, t, 0])
        ys.append(np.einsum("bhpn,bn->bhp", state, Cn[:, t, 0]))
    y_ref = np.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y), y_ref, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(final), state, atol=2e-4,
                               rtol=2e-4)
