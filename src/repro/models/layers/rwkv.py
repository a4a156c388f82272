"""RWKV6 ("Finch") block: data-dependent-decay WKV recurrence + channel mix.

The WKV heads are independent, so the time-mix is head-TP over `model`
(Megatron-SP: AG(x over seq) -> local full-seq recurrence on the head shard
-> row-sharded output -> RS(seq)). Channel-mix is a standard TP FFN.

Recurrence (per head, state s: [hd, hd]):
  out_t = r_t . (s_{t-1} + (u * k_t) v_t^T)
  s_t   = diag(w_t) s_{t-1} + k_t v_t^T
with w_t = exp(-exp(decay_t)) data-dependent via a small LoRA.

Simplifications vs the release: the 5-way token-shift
mixing LoRA is collapsed to a single learned interpolation per stream, and
output gating uses SiLU. The communication/compute structure — which is what
this systems paper prices — is unchanged.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers.common import dtype_of
from repro.sharding.dist import Dist
from repro.sharding.plans import ShardingPlan


def _dims(cfg):
    hd = cfg.rwkv.head_dim
    n_heads = cfg.d_model // hd
    return n_heads, hd


def init_rwkv_tm(cfg, plan: ShardingPlan, key):
    """Time-mix params. Head dim sharded over tp via column blocks."""
    d = cfg.d_model
    nh, hd = _dims(cfg)
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 8)
    sc = d ** -0.5
    lora = max(32, d // 64)
    params = {
        "mix": jnp.full((4, d), 0.5, dt),                  # r,k,v,w shift mixes
        "w_r": jax.random.normal(ks[0], (d, d), dt) * sc,
        "w_k": jax.random.normal(ks[1], (d, d), dt) * sc,
        "w_v": jax.random.normal(ks[2], (d, d), dt) * sc,
        "w_g": jax.random.normal(ks[3], (d, d), dt) * sc,
        "decay_lora_a": jax.random.normal(ks[4], (d, lora), dt) * sc,
        "decay_lora_b": jax.random.normal(ks[5], (lora, d), dt) * (lora ** -0.5),
        "decay_base": jnp.full((d,), -4.0, jnp.float32),
        "bonus": jnp.zeros((d,), jnp.float32),             # u term, per channel
        "w_o": jax.random.normal(ks[6], (d, d), dt) * sc,
    }
    tp = plan.tp_axis
    specs = {
        "mix": P(None, None),
        "w_r": P(None, tp), "w_k": P(None, tp), "w_v": P(None, tp),
        "w_g": P(None, tp),
        "decay_lora_a": P(None, None), "decay_lora_b": P(None, tp),
        "decay_base": P(tp), "bonus": P(tp),
        "w_o": P(tp, None),
    }
    return params, specs


def init_rwkv_cm(cfg, plan: ShardingPlan, key):
    """Channel-mix params (relu^2 FFN, TP over d_ff)."""
    d, dff = cfg.d_model, cfg.d_ff
    dt = dtype_of(cfg)
    k1, k2 = jax.random.split(key)
    params = {
        "mix": jnp.full((d,), 0.5, dt),
        "w_in": jax.random.normal(k1, (d, dff), dt) * (d ** -0.5),
        "w_out": jax.random.normal(k2, (dff, d), dt) * (dff ** -0.5),
    }
    specs = {"mix": P(None), "w_in": P(None, plan.tp_axis),
             "w_out": P(plan.tp_axis, None)}
    return params, specs


def _wkv_scan(r, k, v, w, u, s0, chunk: int = 64):
    """WKV recurrence. r,k,v: [B, S, nh, hd]; w: [B, S, nh, hd] decay in (0,1);
    u: [nh, hd]; s0: [B, nh, hd, hd]. Returns (out [B,S,nh,hd] f32, s_fin)."""
    B, S, nh, hd = r.shape
    ck = min(chunk, S)
    pad = (-S) % ck
    if pad:
        z = ((0, 0), (0, pad), (0, 0), (0, 0))
        r, k, v = jnp.pad(r, z), jnp.pad(k, z), jnp.pad(v, z)
        w = jnp.pad(w, z, constant_values=1.0)
    n = (S + pad) // ck

    def reshape(x):
        return x.reshape(B, n, ck, nh, hd).transpose(1, 0, 2, 3, 4)

    rc, kc, vc, wc = map(reshape, (r, k, v, w))

    def chunk_body(s, inp):
        r_c, k_c, v_c, w_c = inp                             # [B, ck, nh, hd]

        def step(s_, t):
            r_t, k_t, v_t, w_t = (r_c[:, t], k_c[:, t], v_c[:, t], w_c[:, t])
            kv = k_t[..., :, None] * v_t[..., None, :]       # [B,nh,hd,hd]
            out_t = jnp.einsum("bhk,bhkd->bhd", r_t, s_ + u[..., None] * kv)
            s_next = w_t[..., None] * s_ + kv
            return s_next, out_t

        s_fin, out_c = jax.lax.scan(step, s, jnp.arange(ck))
        return s_fin, out_c.transpose(1, 0, 2, 3)            # [B, ck, nh, hd]

    s_fin, out = jax.lax.scan(chunk_body, s0, (rc, kc, vc, wc))
    out = out.transpose(1, 0, 2, 3, 4).reshape(B, n * ck, nh, hd)[:, :S]
    return out, s_fin


def _tm_inputs(params, xg, x_prev, nh_loc, hd):
    """Compute r,k,v,g,w streams from token-shifted input.
    xg: [B, S, D]; x_prev: [B, S, D] (previous token)."""
    mix = params["mix"].astype(jnp.float32)
    xf = xg.astype(jnp.float32)
    pf = x_prev.astype(jnp.float32)

    def mixed(i):
        return (xf * mix[i] + pf * (1 - mix[i])).astype(xg.dtype)

    r = mixed(0) @ params["w_r"]
    k = mixed(1) @ params["w_k"]
    v = mixed(2) @ params["w_v"]
    g = mixed(2) @ params["w_g"]
    decay = (mixed(3) @ params["decay_lora_a"]) @ params["decay_lora_b"]
    w = jnp.exp(-jnp.exp(decay.astype(jnp.float32)
                         + params["decay_base"]))            # (0,1)
    B, S = xg.shape[0], xg.shape[1]

    def heads(x):
        return x.reshape(B, S, nh_loc, hd)

    return (heads(r).astype(jnp.float32), heads(k).astype(jnp.float32),
            heads(v).astype(jnp.float32), g, heads(w))


def rwkv_tm_fwd(params, x, cfg, plan: ShardingPlan, dist: Dist, *,
                make_cache: bool = False):
    """Time-mix. x: [B, S_loc, D] seq-sharded."""
    nh, hd = _dims(cfg)
    seq_ax = plan.seq_axis
    B = x.shape[0]
    xg = dist.all_gather(x, seq_ax, dim=1)                   # [B, S, D]
    S = xg.shape[1]
    x_prev = jnp.pad(xg, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    nh_loc = params["w_r"].shape[-1] // hd

    r, k, v, g, w = _tm_inputs(params, xg, x_prev, nh_loc, hd)
    u = params["bonus"].astype(jnp.float32).reshape(nh_loc, hd)
    s0 = jnp.zeros((B, nh_loc, hd, hd), jnp.float32)
    out, s_fin = _wkv_scan(r, k, v, w, u, s0)
    out = (out.reshape(B, S, -1) * jax.nn.silu(g.astype(jnp.float32))).astype(x.dtype)
    y = out @ params["w_o"]
    y = dist.reduce_scatter(y, seq_ax, dim=1)

    cache = None
    if make_cache:
        cache = {"wkv": s_fin, "shift": xg[:, -1]}
    return y, cache


def rwkv_tm_decode(params, x, cache, cfg, plan: ShardingPlan, dist: Dist):
    """x: [B, 1, D] replicated over tp; cache: wkv [B, nh_loc, hd, hd],
    shift [B, D]."""
    nh, hd = _dims(cfg)
    B = x.shape[0]
    xt = x[:, 0]
    nh_loc = params["w_r"].shape[-1] // hd
    r, k, v, g, w = _tm_inputs(params, xt[:, None], cache["shift"][:, None],
                               nh_loc, hd)
    r, k, v, w = r[:, 0], k[:, 0], v[:, 0], w[:, 0]          # [B, nh_loc, hd]
    u = params["bonus"].astype(jnp.float32).reshape(nh_loc, hd)
    s = cache["wkv"]
    kv = k[..., :, None] * v[..., None, :]
    out = jnp.einsum("bhk,bhkd->bhd", r, s + u[..., None] * kv)
    s_new = w[..., None] * s + kv
    out = (out.reshape(B, -1) * jax.nn.silu(g[:, 0].astype(jnp.float32))).astype(x.dtype)
    y = out @ params["w_o"]
    y = dist.psum(y, plan.tp_axis)
    return y[:, None], {"wkv": s_new, "shift": xt}


def rwkv_cm_fwd(params, x, plan: ShardingPlan, dist: Dist, *,
                make_cache: bool = False):
    """Channel-mix. x: [B, S_loc, D] seq-sharded (or decode [B, 1, D])."""
    seq_ax = plan.seq_axis
    seq_sharded = seq_ax is not None and dist.size(seq_ax) > 1
    xg = dist.all_gather(x, seq_ax, dim=1) if seq_sharded else x
    x_prev = jnp.pad(xg, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    mix = params["mix"].astype(jnp.float32)
    mixed = (xg.astype(jnp.float32) * mix
             + x_prev.astype(jnp.float32) * (1 - mix)).astype(x.dtype)
    h = jnp.square(jax.nn.relu((mixed @ params["w_in"]).astype(jnp.float32)))
    y = h.astype(x.dtype) @ params["w_out"]
    if seq_sharded:
        y = dist.reduce_scatter(y, seq_ax, dim=1)
    else:
        y = dist.psum(y, plan.tp_axis)
    cache = {"shift": xg[:, -1]} if make_cache else None
    return y, cache


def rwkv_cm_decode(params, x, cache, plan: ShardingPlan, dist: Dist):
    """x: [B, 1, D] replicated; cache: shift [B, D]."""
    xt = x[:, 0]
    mix = params["mix"].astype(jnp.float32)
    mixed = (xt.astype(jnp.float32) * mix
             + cache["shift"].astype(jnp.float32) * (1 - mix)).astype(x.dtype)
    h = jnp.square(jax.nn.relu((mixed @ params["w_in"]).astype(jnp.float32)))
    y = h.astype(x.dtype) @ params["w_out"]
    y = dist.psum(y, plan.tp_axis)
    return y[:, None], {"shift": xt}
