"""Pure-jnp oracles (the allclose references): the grouped expert matmul
that the Pallas kernel computes, and decode attention."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def moe_gmm_ref(x, w_gate, w_up, w_down):
    """Grouped expert SwiGLU FFN.
    x: [E, T, D]; w_gate/w_up: [E, D, F]; w_down: [E, F, D] -> [E, T, D]."""
    g = jnp.einsum("etd,edf->etf", x, w_gate)
    u = jnp.einsum("etd,edf->etf", x, w_up)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.einsum("etf,efd->etd", h, w_down)


def decode_attention_ref(q, k, v, length):
    """Single-token decode attention, in float32 throughout.
    q: [B, H, hd]; k/v: [B, KH, S, hd]; length: int, scalar or [B] —
    number of valid positions (of each row). Returns [B, H, hd]."""
    B, H, hd = q.shape
    KH, S = k.shape[1], k.shape[2]
    g = H // KH
    qr = q.reshape(B, KH, g, hd).astype(jnp.float32)
    s = jnp.einsum("bhgd,bhsd->bhgs", qr, k.astype(jnp.float32))
    s = s / jnp.sqrt(jnp.float32(hd))
    mask = jnp.arange(S) < jnp.reshape(jnp.asarray(length), (-1, 1))
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgs,bhsd->bhgd", p, v.astype(jnp.float32))
    return o.reshape(B, H, hd).astype(q.dtype)
