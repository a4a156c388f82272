"""Per-kernel validation: Pallas (interpret=True on CPU) and the decode
path's attention vs the pure-jnp oracles, swept over shapes and dtypes.
The hypothesis property tests live in test_kernels_props.py behind
pytest.importorskip, so a missing `hypothesis` degrades to a skip instead
of killing collection."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.moe_gmm import moe_gmm_pallas
from repro.models.layers import attention
from repro.sharding.dist import NullDist


def rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype) * 0.3


TOLS = {jnp.bfloat16: dict(atol=5e-2, rtol=5e-2),
        jnp.float32: dict(atol=2e-5, rtol=2e-5)}


# ---------------------------------------------------------------------------
# moe_gmm: grouped expert SwiGLU
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("e,t,d,f", [
    (2, 128, 64, 256),       # canonical tile boundary
    (4, 256, 128, 512),      # multiple tiles both axes
    (1, 128, 256, 256),      # single expert
    (3, 384, 64, 768),       # non-power-of-two expert count / tiles
])
def test_moe_gmm_matches_ref(e, t, d, f, dtype):
    ks = jax.random.split(jax.random.PRNGKey(e * 1000 + t), 4)
    x = rand(ks[0], (e, t, d), dtype)
    wg = rand(ks[1], (e, d, f), dtype)
    wu = rand(ks[2], (e, d, f), dtype)
    wd = rand(ks[3], (e, f, d), dtype)
    got = np.asarray(moe_gmm_pallas(x, wg, wu, wd, interpret=True),
                     np.float32)
    want = np.asarray(ref.moe_gmm_ref(x, wg, wu, wd), np.float32)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, **TOLS[dtype])
        return
    # bf16: the kernel accumulates in f32, the oracle in bf16 — they are
    # two equally-valid roundings. Assert the kernel is at least as close
    # to the f32 ground truth as the bf16 oracle is.
    truth = np.asarray(ref.moe_gmm_ref(*(a.astype(jnp.float32)
                                         for a in (x, wg, wu, wd))))
    err_kernel = np.abs(got - truth).max()
    err_oracle = np.abs(want - truth).max()
    assert err_kernel <= err_oracle * 1.5 + 1e-3, (err_kernel, err_oracle)


@pytest.mark.parametrize("block_t,block_f", [(64, 128), (128, 256),
                                             (128, 128), (64, 512)])
def test_moe_gmm_block_shapes(block_t, block_f):
    """Output must be block-shape invariant (pure tiling change)."""
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    e, t, d, f = 2, 128, 64, 512
    x = rand(ks[0], (e, t, d), jnp.float32)
    wg = rand(ks[1], (e, d, f), jnp.float32)
    wu = rand(ks[2], (e, d, f), jnp.float32)
    wd = rand(ks[3], (e, f, d), jnp.float32)
    got = moe_gmm_pallas(x, wg, wu, wd, block_t=block_t, block_f=block_f,
                         interpret=True)
    want = ref.moe_gmm_ref(x, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("e,t,d,f", [
    (2, 100, 64, 300),       # t and f both off the tile boundary
    (1, 7, 32, 130),         # tiny t -> block_t shrinks to t
    (3, 130, 64, 256),       # t just past one tile
])
def test_moe_gmm_unaligned_shapes(e, t, d, f):
    """Arbitrary capacity factors: non-tile-multiple t/f zero-pad instead of
    crashing."""
    ks = jax.random.split(jax.random.PRNGKey(t * 10 + f), 4)
    x = rand(ks[0], (e, t, d), jnp.float32)
    wg = rand(ks[1], (e, d, f), jnp.float32)
    wu = rand(ks[2], (e, d, f), jnp.float32)
    wd = rand(ks[3], (e, f, d), jnp.float32)
    got = moe_gmm_pallas(x, wg, wu, wd, block_t=64, block_f=128,
                         interpret=True)
    want = ref.moe_gmm_ref(x, wg, wu, wd)
    assert got.shape == (e, t, d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5,
                               rtol=3e-5)


def test_moe_gmm_expert_independence():
    """Zeroing expert i's tokens must not change expert j's output."""
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    e, t, d, f = 3, 64, 32, 128
    x = rand(ks[0], (e, t, d), jnp.float32)
    wg = rand(ks[1], (e, d, f), jnp.float32)
    wu = rand(ks[2], (e, d, f), jnp.float32)
    wd = rand(ks[3], (e, f, d), jnp.float32)
    base = moe_gmm_pallas(x, wg, wu, wd, block_t=64, block_f=128,
                          interpret=True)
    x2 = x.at[0].set(0.0)
    out = moe_gmm_pallas(x2, wg, wu, wd, block_t=64, block_f=128,
                         interpret=True)
    np.testing.assert_allclose(np.asarray(out[1:]), np.asarray(base[1:]),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(out[0]), 0.0, atol=1e-6)


# ---------------------------------------------------------------------------
# decode attention: attention.attn_chunk_lse, normalised as attention_decode
# normalises it, against the float32 oracle
# ---------------------------------------------------------------------------

def decode_attention(q, k, v, max_pos):
    """The decode path's attention over a whole [B, KH, S, hd] cache, each
    row attending to positions 0..max_pos (inclusive; scalar or [B])."""
    o, m, lsum = attention.attn_chunk_lse(
        q, k, v, pos_k=jnp.arange(k.shape[2]), max_pos=max_pos)
    return attention.lse_combine(o, m, lsum, None, NullDist()).astype(q.dtype)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("b,h,kh,s,hd", [
    (2, 8, 8, 512, 64),      # MHA
    (2, 8, 2, 1024, 64),     # GQA 4:1
    (1, 16, 1, 2048, 128),   # MQA, long S
    (4, 4, 4, 512, 32),
])
def test_decode_attention_matches_ref(b, h, kh, s, hd, dtype):
    ks = jax.random.split(jax.random.PRNGKey(b * 100 + s), 3)
    q = rand(ks[0], (b, h, hd), dtype)
    k = rand(ks[1], (b, kh, s, hd), dtype)
    v = rand(ks[2], (b, kh, s, hd), dtype)
    length = s - 3
    got = decode_attention(q, k, v, jnp.int32(length - 1))
    want = ref.decode_attention_ref(q, k, v, length)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOLS[dtype])


@pytest.mark.parametrize("lengths", [
    (700, 700, 700, 700),    # every row the same
    (129, 333, 700, 1000),   # every row different
    (700, 1, 700, 700),      # one row at its first position
    (700, 700, 1024, 700),   # one row at the cache's last position
], ids=["equal", "distinct", "one-at-1", "one-at-full"])
def test_decode_attention_per_row_lengths(lengths):
    """Each row masks at its own position (`max_pos [B]`), as the batched
    decode wave does with slots at different positions."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    b, h, kh, s, hd = len(lengths), 4, 2, 1024, 64
    q = rand(ks[0], (b, h, hd), jnp.float32)
    k = rand(ks[1], (b, kh, s, hd), jnp.float32)
    v = rand(ks[2], (b, kh, s, hd), jnp.float32)
    length = jnp.asarray(lengths, jnp.int32)
    got = decode_attention(q, k, v, length - 1)
    want = ref.decode_attention_ref(q, k, v, length)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5,
                               rtol=3e-5)


def test_decode_attention_convex_combination():
    """The output is a convex combination of V rows: within their min/max
    envelope."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    b, h, kh, s, hd = 1, 2, 1, 256, 16
    q = rand(ks[0], (b, h, hd), jnp.float32)
    k = rand(ks[1], (b, kh, s, hd), jnp.float32)
    v = rand(ks[2], (b, kh, s, hd), jnp.float32)
    out = decode_attention(q, k, v, jnp.int32(s - 1))
    vmin = np.asarray(v.min(axis=2))[:, :, None]
    vmax = np.asarray(v.max(axis=2))[:, :, None]
    o = np.asarray(out).reshape(b, kh, -1, hd)
    assert (o >= vmin - 1e-4).all() and (o <= vmax + 1e-4).all()
