"""Attention layers.

Three execution paths:

train / prefill (tokens seq-sharded over `model`):
  head_tp     AG(x over seq) -> q on local head shard, K/V on the single KV
              head this rank's q-group maps to -> local chunked flash
              attention over the full sequence -> row-sharded W_o ->
              reduce-scatter(seq).  (Megatron-SP schedule.)
  replicated  weights replicated (small archs): q stays seq-local, K/V
              all-gathered over seq (cheap: kv_heads * hd << D), no other
              collectives.

decode (tokens replicated over `model`, KV cache sequence-sharded):
  every rank computes attention of the full-head query against its local KV
  chunk, partial results merged with the log-sum-exp trick
  (pmax m, psum lsum*e^{m-M}, psum o*e^{m-M}).

Prefill writes the cache in exactly the decode layout:
  global layers  k,v: [B, KV, S_loc, hd]  (seq-sharded over `model`)
  local  layers  k,v: [B, KV, W, hd]      (ring buffer, replicated)
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers.common import apply_rope, dtype_of
from repro.sharding.dist import Dist
from repro.sharding.plans import ShardingPlan

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# attention cores in plain jnp: chunked flash attention for train and
# prefill, one chunk with its log-sum-exp for decode (attn_chunk_lse, held
# to kernels.ref.decode_attention_ref by tests/test_kernels.py)
# ---------------------------------------------------------------------------

def flash_attn(q, k, v, *, causal: bool, window: int = 0,
               q_offset=0, kv_offset=0, kv_len=None, chunk: int = 1024):
    """Online-softmax attention, chunked over KV.

    q: [B, Sq, H, hd]; k, v: [B, Sk, KH, hd]  (H % KH == 0)
    q_offset / kv_offset: absolute position of element 0 (int or traced).
    kv_len: number of valid kv positions (defaults to Sk).
    Returns [B, Sq, H, hd].
    """
    B, Sq, H, hd = q.shape
    Sk, KH = k.shape[1], k.shape[2]
    g = H // KH
    scale = 1.0 / math.sqrt(hd)
    kv_len = Sk if kv_len is None else kv_len

    ck = min(chunk, Sk)
    pad = (-Sk) % ck
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_chunks = (Sk + pad) // ck

    qr = jnp.transpose(q.reshape(B, Sq, KH, g, hd), (0, 2, 3, 1, 4))  # [B,KH,g,Sq,hd]
    kc = jnp.transpose(k.reshape(B, n_chunks, ck, KH, hd), (1, 0, 3, 2, 4))
    vc = jnp.transpose(v.reshape(B, n_chunks, ck, KH, hd), (1, 0, 3, 2, 4))
    pos_q = q_offset + jnp.arange(Sq)

    def body(carry, inp):
        m, lsum, acc = carry
        ci, kci, vci = inp
        pos_k = kv_offset + ci * ck + jnp.arange(ck)
        # bf16-native matmuls with f32 accumulation (MXU-style): never
        # materialize an f32 copy of K/V, which would double the HBM
        # traffic
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qr, kci,
                       preferred_element_type=jnp.float32) * scale
        mask = (pos_k[None, :] < kv_len)
        if causal:
            mask &= pos_k[None, :] <= pos_q[:, None]
        if window:
            mask &= pos_q[:, None] - pos_k[None, :] < window
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = lsum * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhgqk,bhkd->bhgqd", p.astype(vci.dtype), vci,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KH, g, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KH, g, Sq), jnp.float32)
    a0 = jnp.zeros((B, KH, g, Sq, hd), jnp.float32)
    (m, lsum, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (jnp.arange(n_chunks), kc, vc))
    out = acc / jnp.maximum(lsum, 1e-30)[..., None]
    out = jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(B, Sq, H, hd)
    return out.astype(q.dtype)


def attn_chunk_lse(q, k, v, *, pos_k, max_pos):
    """Single-chunk decode attention returning unnormalized (o, m, lsum) for the
    cross-rank log-sum-exp combine.

    q: [B, H, hd]; k, v: [B, KH, S_loc, hd]; pos_k: [S_loc] or [B, S_loc]
    absolute positions; max_pos: highest attendable position (inclusive),
    scalar or [B].
    Returns o: [B, H, hd] f32 (sum of e^{s-m} v), m: [B, H], lsum: [B, H].
    """
    B, H, hd = q.shape
    KH = k.shape[1]
    g = H // KH
    scale = 1.0 / math.sqrt(hd)
    # bf16-native score/value matmuls with f32 accumulation: the KV cache
    # is read at its own width, never as an f32 copy
    qr = q.reshape(B, KH, g, hd).astype(k.dtype)
    s = jnp.einsum("bhgd,bhsd->bhgs", qr, k,
                   preferred_element_type=jnp.float32) * scale
    mask = (pos_k <= jnp.expand_dims(max_pos, -1))[..., None, None, :]
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(mask, p, 0.0)
    o = jnp.einsum("bhgs,bhsd->bhgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    lsum = jnp.sum(p, axis=-1)
    return o.reshape(B, H, hd), m.reshape(B, H), lsum.reshape(B, H)


def lse_combine(o, m, lsum, axis, dist: Dist):
    """Merge per-rank partial attention (o, m, lsum) over a sharded KV axis."""
    if axis is None or dist.size(axis) == 1:
        return o / jnp.maximum(lsum, 1e-30)[..., None]
    m_g = dist.pmax(jax.lax.stop_gradient(m), axis)
    corr = jnp.exp(m - m_g)
    l_g = dist.psum(lsum * corr, axis)
    o_g = dist.psum(o * corr[..., None], axis)
    return o_g / jnp.maximum(l_g, 1e-30)[..., None]


def ring_attention(q, k, v, *, seq_ax, dist: Dist, causal: bool = True):
    """Ring attention over a sequence-sharded KV.

    q: [B, Sq_loc, H_loc, hd] (local seq chunk, local head shard)
    k, v: [B, Sk_loc, KH_loc, hd] (local seq chunk of the matching KV heads)

    Instead of all-gathering the full activations/KV (Megatron-SP), the KV
    chunk rotates around the `seq_ax` ring via collective_permute while an
    online-softmax state accumulates — per-device collective traffic drops
    from O(S*D) to O(S*KH_loc*hd), and each hop is data-independent of the
    current chunk's attention compute, so XLA's latency-hiding scheduler
    overlaps them. Fully-future chunks are masked (not skipped): simple
    ring, ~2x compute for exact causal semantics (zigzag ordering is the
    known fix; documented as future work).
    """
    B, sq, H_loc, hd = q.shape
    sk, KH_loc = k.shape[1], k.shape[2]
    n = dist.size(seq_ax)
    if n == 1:
        return flash_attn(q, k, v, causal=causal)
    r = dist.index(seq_ax)
    g = H_loc // KH_loc
    scale = 1.0 / math.sqrt(hd)
    pos_q = r * sq + jnp.arange(sq)
    qr = jnp.transpose(q.reshape(B, sq, KH_loc, g, hd),
                       (0, 2, 3, 1, 4))                     # [B,KH,g,Sq,hd]

    def body(carry, step):
        m, lsum, acc, kc, vc = carry
        src = jnp.mod(r - step, n)
        pos_k = src * sk + jnp.arange(sk)
        kt = jnp.transpose(kc, (0, 2, 1, 3))                # [B,KH,Sk,hd]
        vt = jnp.transpose(vc, (0, 2, 1, 3))
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qr, kt,
                       preferred_element_type=jnp.float32) * scale
        mask = jnp.ones((sq, sk), bool)
        if causal:
            mask = pos_k[None, :] <= pos_q[:, None]
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(mask[None, None, None], p, 0.0)
        corr = jnp.exp(m - m_new)
        l_new = lsum * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhgqk,bhkd->bhgqd", p.astype(vt.dtype), vt,
            preferred_element_type=jnp.float32)
        kc = dist.roll(kc, seq_ax, shift=1)
        vc = dist.roll(vc, seq_ax, shift=1)
        return (m_new, l_new, acc_new, kc, vc), None

    m0 = jnp.full((B, KH_loc, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KH_loc, g, sq), jnp.float32)
    a0 = jnp.zeros((B, KH_loc, g, sq, hd), jnp.float32)
    (m, lsum, acc, _, _), _ = jax.lax.scan(
        body, (m0, l0, a0, k, v), jnp.arange(n))
    out = acc / jnp.maximum(lsum, 1e-30)[..., None]
    out = jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(B, sq, H_loc * hd)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# parameter init (global shapes; sliced by shard_map in_specs)
# ---------------------------------------------------------------------------

def init_attention(cfg, plan: ShardingPlan, key, *, cross: bool = False):
    d, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dt = dtype_of(cfg)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    sc = d ** -0.5
    params = {
        "w_q": jax.random.normal(k1, (d, H * hd), dt) * sc,
        "w_k": jax.random.normal(k2, (d, KV, hd), dt) * sc,
        "w_v": jax.random.normal(k3, (d, KV, hd), dt) * sc,
        "w_o": jax.random.normal(k4, (H * hd, d), dt) * ((H * hd) ** -0.5),
    }
    if plan.attn_mode == "head_tp":
        specs = {
            "w_q": P(None, plan.tp_axis),
            "w_k": P(None, None, None),
            "w_v": P(None, None, None),
            "w_o": P(plan.tp_axis, None),
        }
    else:
        specs = {k: P(*([None] * v.ndim)) for k, v in params.items()}
    return params, specs


def _local_kv_slice(cfg, plan: ShardingPlan, dist: Dist):
    """KV head range this rank's q shard maps to under head_tp."""
    tp = dist.size(plan.tp_axis)
    H, KV = cfg.num_heads, cfg.num_kv_heads
    h_loc = H // tp
    kv_loc = max(1, (KV * h_loc) // H)  # == max(1, KV // tp)
    r = dist.index(plan.tp_axis)
    start = (r * h_loc * KV) // H
    return start, kv_loc


# ---------------------------------------------------------------------------
# train / prefill self-attention
# ---------------------------------------------------------------------------

@jax.named_scope("attention")
def attention_fwd(params, x, cfg, plan: ShardingPlan, dist: Dist, *,
                  causal: bool = True, window: int = 0,
                  make_cache: bool = False):
    """x: [B, S_loc, D] seq-sharded (or full under NullDist).
    Returns (y [B, S_loc, D], cache | None)."""
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    seq_ax = plan.seq_axis
    B, s_loc, _ = x.shape
    r_seq = dist.index(seq_ax)
    q_offset = r_seq * s_loc

    cache = None
    if make_cache:
        # cache K/V: all KV heads for the LOCAL seq chunk (decode layout)
        k_c = jnp.einsum("bsd,dkh->bksh", x, params["w_k"])
        v_c = jnp.einsum("bsd,dkh->bksh", x, params["w_v"])
        pos_local = q_offset + jnp.arange(s_loc)
        k_c = jnp.transpose(
            apply_rope(jnp.transpose(k_c, (0, 2, 1, 3)), pos_local,
                       cfg.rope_theta), (0, 2, 1, 3))
        if window:
            cache = _window_cache_from_prefill(k_c, v_c, window, s_loc, plan, dist)
        else:
            cache = {"k": k_c, "v": v_c}

    if plan.attn_mode == "head_tp":
        if plan.ring_attn and window == 0 and dist.size(seq_ax) > 1:
            # ring path: q/k/v from the LOCAL seq chunk only; KV rotates
            # around the seq ring — no full-seq all-gather, no full-seq
            # reduce-scatter.
            q = (x @ params["w_q"]).reshape(B, s_loc, -1, hd)
            start, kv_loc = _local_kv_slice(cfg, plan, dist)
            w_k = jax.lax.dynamic_slice_in_dim(params["w_k"], start, kv_loc,
                                               axis=1)
            w_v = jax.lax.dynamic_slice_in_dim(params["w_v"], start, kv_loc,
                                               axis=1)
            k = jnp.einsum("bsd,dkh->bskh", x, w_k)
            v = jnp.einsum("bsd,dkh->bskh", x, w_v)
            pos_local = q_offset + jnp.arange(s_loc)
            q = apply_rope(q, pos_local, cfg.rope_theta)
            k = apply_rope(k, pos_local, cfg.rope_theta)
            o = ring_attention(q, k, v, seq_ax=seq_ax, dist=dist,
                               causal=causal)
            y = o @ params["w_o"]                 # head-partial [B,S_loc,D]
            y = dist.psum(y, plan.tp_axis)
            return y, cache
        xg = dist.all_gather(x, seq_ax, dim=1)                 # [B, S, D]
        S = xg.shape[1]
        q = (xg @ params["w_q"]).reshape(B, S, -1, hd)         # local heads
        start, kv_loc = _local_kv_slice(cfg, plan, dist)
        w_k = jax.lax.dynamic_slice_in_dim(params["w_k"], start, kv_loc, axis=1)
        w_v = jax.lax.dynamic_slice_in_dim(params["w_v"], start, kv_loc, axis=1)
        k = jnp.einsum("bsd,dkh->bskh", xg, w_k)
        v = jnp.einsum("bsd,dkh->bskh", xg, w_v)
        pos = jnp.arange(S)
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
        o = flash_attn(q, k, v, causal=causal, window=window)
        # w_o is row-sharded over heads: the tiled psum_scatter sums the
        # partial head contributions AND scatters the sequence in one
        # collective (Megatron-SP).
        y = o.reshape(B, S, -1) @ params["w_o"]
        y = dist.reduce_scatter(y, seq_ax, dim=1)
        return y, cache

    # replicated-weight path
    q = (x @ params["w_q"]).reshape(B, s_loc, H, hd)
    k = jnp.einsum("bsd,dkh->bskh", x, params["w_k"])
    v = jnp.einsum("bsd,dkh->bskh", x, params["w_v"])
    pos_local = q_offset + jnp.arange(s_loc)
    q = apply_rope(q, pos_local, cfg.rope_theta)
    k = apply_rope(k, pos_local, cfg.rope_theta)
    k = dist.all_gather(k, seq_ax, dim=1)                      # [B, S, KV, hd]
    v = dist.all_gather(v, seq_ax, dim=1)
    o = flash_attn(q, k, v, causal=causal, window=window, q_offset=q_offset)
    y = o.reshape(B, s_loc, -1) @ params["w_o"]
    return y, cache


def _window_cache_from_prefill(k_c, v_c, window, s_loc, plan, dist):
    """Build the replicated ring-buffer cache for a sliding-window layer from
    the seq-sharded prefill K/V. Only the final `window` positions matter;
    they live on the last rank(s). We all-gather the last `window` positions
    worth (cheap: window << S) via psum of masked contributions."""
    B, KV, _, hd = k_c.shape
    seq_ax = plan.seq_axis
    n = dist.size(seq_ax)
    S = s_loc * n
    r = dist.index(seq_ax)
    pos_local = r * s_loc + jnp.arange(s_loc)
    # ring slot for each local position; valid if within the last `window`
    slot = pos_local % window
    valid = pos_local >= S - window
    k_ring = jnp.zeros((B, KV, window, hd), k_c.dtype)
    v_ring = jnp.zeros((B, KV, window, hd), v_c.dtype)
    k_ring = k_ring.at[:, :, slot, :].add(jnp.where(valid[None, None, :, None], k_c, 0))
    v_ring = v_ring.at[:, :, slot, :].add(jnp.where(valid[None, None, :, None], v_c, 0))
    k_ring = dist.psum(k_ring, seq_ax)
    v_ring = dist.psum(v_ring, seq_ax)
    return {"k": k_ring, "v": v_ring}


# ---------------------------------------------------------------------------
# decode self-attention (KV cache)
# ---------------------------------------------------------------------------

@jax.named_scope("attention")
def attention_decode(params, x, cache, pos, cfg, plan: ShardingPlan,
                     dist: Dist, *, window: int = 0):
    """x: [B, 1, D] (replicated over tp); cache k/v: [B, KV, S_loc, hd]
    (seq-sharded over plan.kv_axis; ring buffer [B, KV, W, hd] if window).
    pos: [B] int32, position of each row's incoming token. Returns
    (y, cache)."""
    hd = cfg.head_dim
    B = x.shape[0]
    xt = x[:, 0]                                              # [B, D]
    tp = dist.size(plan.tp_axis)
    rows = jnp.arange(B)

    q = (xt @ params["w_q"]).reshape(B, -1, hd)
    if plan.attn_mode == "head_tp" and tp > 1:
        q = dist.all_gather(q, plan.tp_axis, dim=1)           # [B, H, hd]
    q = apply_rope(q[:, None], pos[:, None], cfg.rope_theta)[:, 0]

    k_new = jnp.einsum("bd,dkh->bkh", xt, params["w_k"])
    v_new = jnp.einsum("bd,dkh->bkh", xt, params["w_v"])
    k_new = apply_rope(k_new[:, None], pos[:, None], cfg.rope_theta)[:, 0]

    # Each row's token is scattered into its own row alone: rebuilding the
    # cache with a where() made XLA copy the whole cache per layer.
    if window:
        w = cache["k"].shape[2]
        slot = pos % w
        k_c = cache["k"].at[rows, :, slot].set(k_new)
        v_c = cache["v"].at[rows, :, slot].set(v_new)
        slots = jnp.arange(w)
        slot_pos = pos[:, None] - jnp.mod(pos[:, None] - slots, w)  # [B, W]
        # unwritten slots (early decode, pos < window) -> mask out
        slot_pos = jnp.where(slot_pos < 0, jnp.int32(2 ** 30), slot_pos)
        o, m, lsum = attn_chunk_lse(q, k_c, v_c, pos_k=slot_pos, max_pos=pos)
        o = o / jnp.maximum(lsum, 1e-30)[..., None]
    else:
        s_loc = cache["k"].shape[2]
        kv_ax = plan.kv_axis
        r = dist.index(kv_ax)
        local = pos - r * s_loc
        # only the rank that owns a row's position writes it; the others,
        # and a dead slot whose position ran past the cache, drop the write
        local = jnp.where((local >= 0) & (local < s_loc), local, s_loc)
        k_c = cache["k"].at[rows, :, local].set(k_new, mode="drop")
        v_c = cache["v"].at[rows, :, local].set(v_new, mode="drop")
        pos_k = r * s_loc + jnp.arange(s_loc)
        o, m, lsum = attn_chunk_lse(q, k_c, v_c, pos_k=pos_k, max_pos=pos)
        o = lse_combine(o, m, lsum, kv_ax, dist)

    cache = {"k": k_c, "v": v_c}
    y = _decode_out_proj(o, params, plan, dist, B)
    return y, cache


def _decode_out_proj(o, params, plan: ShardingPlan, dist: Dist, B):
    """o: [B, H, hd] f32 full heads on every rank; W_o may be row-sharded."""
    tp = dist.size(plan.tp_axis)
    w_o = params["w_o"]
    if plan.attn_mode == "head_tp" and tp > 1:
        hh_loc = w_o.shape[0]
        r = dist.index(plan.tp_axis)
        o_loc = jax.lax.dynamic_slice_in_dim(
            o.reshape(B, -1), r * hh_loc, hh_loc, axis=1)
        y = o_loc.astype(w_o.dtype) @ w_o
        y = dist.psum(y, plan.tp_axis)
    else:
        y = o.reshape(B, -1).astype(w_o.dtype) @ w_o
    return y[:, None, :]


# ---------------------------------------------------------------------------
# cross-attention (encoder-decoder)
# ---------------------------------------------------------------------------

def cross_attention_fwd(params, x, enc_kv, cfg, plan: ShardingPlan,
                        dist: Dist):
    """Training/prefill cross-attention. x: [B, S_loc, D] decoder tokens;
    enc_kv: {"k","v"} [B, KV, Se_loc, hd] seq-sharded encoder cache."""
    hd = cfg.head_dim
    B, s_loc, _ = x.shape
    tp = dist.size(plan.tp_axis)
    k = jnp.transpose(enc_kv["k"], (0, 2, 1, 3))             # [B, Se_loc, KV, hd]
    v = jnp.transpose(enc_kv["v"], (0, 2, 1, 3))
    if plan.attn_mode == "head_tp" and tp > 1:
        # Megatron-SP: full-seq q on the local head shard, matching KV head.
        xg = dist.all_gather(x, plan.seq_axis, dim=1)
        q = (xg @ params["w_q"]).reshape(B, xg.shape[1], -1, hd)
        start, kv_loc = _local_kv_slice(cfg, plan, dist)
        k = jax.lax.dynamic_slice_in_dim(k, start, kv_loc, axis=2)
        v = jax.lax.dynamic_slice_in_dim(v, start, kv_loc, axis=2)
        k = dist.all_gather(k, plan.seq_axis, dim=1)
        v = dist.all_gather(v, plan.seq_axis, dim=1)
        o = flash_attn(q, k, v, causal=False)
        y = o.reshape(B, o.shape[1], -1) @ params["w_o"]     # head-partial
        return dist.reduce_scatter(y, plan.seq_axis, dim=1)
    q = (x @ params["w_q"]).reshape(B, s_loc, -1, hd)
    k = dist.all_gather(k, plan.seq_axis, dim=1)
    v = dist.all_gather(v, plan.seq_axis, dim=1)
    o = flash_attn(q, k, v, causal=False)
    return o.reshape(B, s_loc, -1) @ params["w_o"]


def cross_attention_decode(params, x, enc_kv, enc_len, cfg,
                           plan: ShardingPlan, dist: Dist):
    """Decode-time cross-attention: x [B, 1, D]; enc_kv seq-sharded."""
    B = x.shape[0]
    hd = cfg.head_dim
    xt = x[:, 0]
    tp = dist.size(plan.tp_axis)
    q = (xt @ params["w_q"]).reshape(B, -1, hd)
    if plan.attn_mode == "head_tp" and tp > 1:
        q = dist.all_gather(q, plan.tp_axis, dim=1)
    s_loc = enc_kv["k"].shape[2]
    r = dist.index(plan.kv_axis)
    pos_k = r * s_loc + jnp.arange(s_loc)
    o, m, lsum = attn_chunk_lse(q, enc_kv["k"], enc_kv["v"], pos_k=pos_k,
                             max_pos=enc_len - 1)
    o = lse_combine(o, m, lsum, plan.kv_axis, dist)
    return _decode_out_proj(o, params, plan, dist, B)


def make_enc_cache(params, enc_out, cfg, plan: ShardingPlan, dist: Dist):
    """Precompute the (read-only) encoder K/V for decoder cross-attention.
    enc_out: [B, Se_loc, D] seq-sharded -> k/v [B, KV, Se_loc, hd]."""
    k = jnp.einsum("bsd,dkh->bksh", enc_out, params["w_k"])
    v = jnp.einsum("bsd,dkh->bksh", enc_out, params["w_v"])
    return {"k": k, "v": v}
