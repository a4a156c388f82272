"""Hypothesis property tests for the Pallas kernel and decode attention.

Kept separate from test_kernels.py so a missing `hypothesis` (an optional
[dev] dependency) skips this module instead of erroring the whole suite at
collection.
"""
import pytest

pytest.importorskip("hypothesis")

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.moe_gmm import moe_gmm_pallas
from repro.models.layers import attention
from repro.sharding.dist import NullDist


def rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype) * 0.3


def decode_attention(q, k, v, max_pos):
    """As in test_kernels.py: attn_chunk_lse normalised as attention_decode
    normalises it."""
    o, m, lsum = attention.attn_chunk_lse(
        q, k, v, pos_k=jnp.arange(k.shape[2]), max_pos=max_pos)
    return attention.lse_combine(o, m, lsum, None, NullDist()).astype(q.dtype)


@given(e=st.integers(1, 3), nt=st.integers(1, 3), nf=st.integers(1, 3),
       seed=st.integers(0, 2**16))
@settings(max_examples=12, deadline=None)
def test_moe_gmm_property(e, nt, nf, seed):
    """Property: any (expert, tile-count) combination matches the oracle."""
    t, d, f = 64 * nt, 32, 128 * nf
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = rand(ks[0], (e, t, d), jnp.float32)
    wg = rand(ks[1], (e, d, f), jnp.float32)
    wu = rand(ks[2], (e, d, f), jnp.float32)
    wd = rand(ks[3], (e, f, d), jnp.float32)
    got = moe_gmm_pallas(x, wg, wu, wd, block_t=64, block_f=128,
                         interpret=True)
    want = ref.moe_gmm_ref(x, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5,
                               rtol=3e-5)


@given(length_fracs=st.lists(st.floats(0.0, 1.0), min_size=3, max_size=3),
       seed=st.integers(0, 2**16))
@settings(max_examples=12, deadline=None)
def test_decode_attention_length_property(length_fracs, seed):
    """Property: masking each row at its own `max_pos` equals physically
    truncating that row's K/V."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    b, h, kh, s, hd = len(length_fracs), 4, 2, 512, 32
    q = rand(ks[0], (b, h, hd), jnp.float32)
    k = rand(ks[1], (b, kh, s, hd), jnp.float32)
    v = rand(ks[2], (b, kh, s, hd), jnp.float32)
    lengths = [max(int(s * f), 1) for f in length_fracs]
    got = decode_attention(q, k, v, jnp.asarray(lengths, jnp.int32) - 1)
    for row, n in enumerate(lengths):
        want = ref.decode_attention_ref(q[row:row + 1], k[row:row + 1, :, :n],
                                        v[row:row + 1, :, :n], n)
        np.testing.assert_allclose(np.asarray(got[row:row + 1]),
                                   np.asarray(want), atol=3e-5, rtol=3e-5)
