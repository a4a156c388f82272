"""Expert-parallel Mixture-of-Experts layer (static capacity, scatter-based).

This is the paper's central communication pattern: tokens are dispatched to
the devices hosting their routed experts with an explicit
``jax.lax.all_to_all`` (the A2A the paper's alpha-beta model prices),
computed by the grouped expert matmul (Pallas kernel on TPU), and gathered
back with the mirror all-to-all.

Token layout: x [B, T_loc, D] — the local token slice on each rank of the EP
axis (train/prefill: seq-sharded tokens; decode: batch-sharded tokens).
Experts are padded up to a multiple of the EP group (e.g. granite 40 -> 48);
padded experts receive -inf router logits and are never routed to.

Dispatch uses scatter-add into the [E, C, D] expert buffers (and a gather on
the way back) instead of the GShard one-hot einsum: O(T*k*D) work and no
[T, E, C] tensor, matching how production systems build A2A payloads.

EP trace (per rank, E = padded experts, L = E / ep local experts, C = capacity):
  router     [T_loc, E]
  scatter    -> x_e [E, C, D]
  all_to_all (split expert dim, concat capacity dim)  -> [L, ep*C, D]
  expert FFN (grouped matmul kernel)                  -> [L, ep*C, D]
  all_to_all back                                     -> [E, C, D]
  gather+weighted-sum                                 -> y [T_loc, D]
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers.common import dtype_of
from repro.sharding.dist import Dist
from repro.sharding.plans import ShardingPlan

from repro.kernels import ops as kops


def fp8_dispatch_a2a(x_e, ep_ax, dist: Dist):
    """fp8(e4m3) wire format for the dispatch all-to-all (DeepSeek-V3's
    production scheme: fp8 dispatch, bf16 combine). Per-slot scales ride
    along; the uint8 bitcast pins the 1-byte wire width against XLA's
    convert hoisting / f8-collective promotion."""
    xf = x_e.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    q = (xf / scale).astype(jnp.float8_e4m3fn)
    qb = jax.lax.bitcast_convert_type(q, jnp.uint8)
    qg = dist.all_to_all(qb, ep_ax, split_dim=0, concat_dim=1)
    sg = dist.all_to_all(scale, ep_ax, split_dim=0, concat_dim=1)
    qg = jax.lax.bitcast_convert_type(
        jax.lax.optimization_barrier(qg), jnp.float8_e4m3fn)
    return (qg.astype(jnp.float32) * sg).astype(x_e.dtype)


def capacity(t_loc: int, topk: int, n_exp: int, cf: float) -> int:
    c = int(-(-t_loc * topk * cf // n_exp))
    return max(c, 1)


def init_moe(cfg, plan: ShardingPlan, key):
    m = cfg.moe
    ep = plan.ep
    e_pad = m.padded_num_experts(max(ep, 1))
    d, de = cfg.d_model, m.d_expert
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 7)
    params = {
        "router": jax.random.normal(ks[0], (d, e_pad), jnp.float32) * (d ** -0.5),
        "w_gate": jax.random.normal(ks[1], (e_pad, d, de), dt) * (d ** -0.5),
        "w_up": jax.random.normal(ks[2], (e_pad, d, de), dt) * (d ** -0.5),
        "w_down": jax.random.normal(ks[3], (e_pad, de, d), dt) * (de ** -0.5),
    }
    specs = {
        "router": P(None, None),
        "w_gate": P(plan.ep_axis, None, None),
        "w_up": P(plan.ep_axis, None, None),
        "w_down": P(plan.ep_axis, None, None),
    }
    if m.num_shared_experts:
        dsh = m.d_shared_expert * m.num_shared_experts
        params["w_shared_gate"] = jax.random.normal(ks[4], (d, dsh), dt) * (d ** -0.5)
        params["w_shared_up"] = jax.random.normal(ks[5], (d, dsh), dt) * (d ** -0.5)
        params["w_shared_down"] = jax.random.normal(ks[6], (dsh, d), dt) * (dsh ** -0.5)
        specs["w_shared_gate"] = P(None, plan.tp_axis)
        specs["w_shared_up"] = P(None, plan.tp_axis)
        specs["w_shared_down"] = P(plan.tp_axis, None)
    return params, specs


def route(logits, topk: int, n_real: int):
    """logits [T, E] fp32 (E includes padding). Returns (gates [T,k],
    idx [T,k], probs [T,E]) with padded experts masked out."""
    e = logits.shape[-1]
    mask = jnp.arange(e) < n_real
    logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, topk)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    return gates, idx, probs


def slot_assignment(idx, e_pad: int, cap: int):
    """Queue position of each (token, k) routing decision in its expert's
    capacity buffer, token-major priority. idx: [T, k] ->
    (slot [T, k] int32, keep [T, k] bool)."""
    t, k = idx.shape
    onehot = jax.nn.one_hot(idx.reshape(t * k), e_pad, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - onehot                    # [T*k, E]
    slot = jnp.take_along_axis(pos, idx.reshape(t * k, 1), axis=1)[:, 0]
    slot = slot.reshape(t, k)
    keep = slot < cap
    return slot.astype(jnp.int32), keep


def aux_load_balance_loss(probs, idx, n_real: int):
    """Switch-transformer load-balance loss over the real experts."""
    e = probs.shape[-1]
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32).sum(1)     # [T, E]
    frac_tokens = jnp.mean(onehot, axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    return n_real * jnp.sum(frac_tokens * frac_probs)


def moe_ffn(params, x, cfg, plan: ShardingPlan, dist: Dist,
            *, decode: bool = False, collect_aux: bool = False):
    """x: [B, T_loc, D] local token slice on each EP rank.
    Returns (y, aux_loss).

    Capacity: the tokens of a prompt (train, prefill) are one group, given
    `capacity_factor` of the even share each. In decode (x [B, 1, D], one
    token of each of B requests) every token is a group of its own, as if
    decoded alone: each expert gets B rows, which hold every routing
    decision (a token picks an expert at most once), so none is dropped
    and no row's result depends on another's."""
    m = cfg.moe
    B, t, d = x.shape
    xt = x.reshape(B * t, d)
    n_tok = B * t
    ep_ax = plan.ep_axis
    ep = dist.size(ep_ax)
    e_pad = params["router"].shape[-1]
    cap = n_tok if decode else capacity(n_tok, m.experts_per_token, e_pad,
                                        m.capacity_factor)

    with jax.named_scope("router"):
        logits = xt.astype(jnp.float32) @ params["router"]
        gates, idx, probs = route(logits, m.experts_per_token, m.num_experts)
        slot, keep = slot_assignment(idx, e_pad, cap)

    with jax.named_scope("dispatch"):
        # scatter tokens into [E*C, D] expert buffers; flat_idx [T*k]
        flat_idx = (idx * cap + jnp.clip(slot, 0, cap - 1)).reshape(-1)
        contrib = (xt[:, None, :] * keep[..., None].astype(xt.dtype))
        x_e = jnp.zeros((e_pad * cap, d), xt.dtype).at[flat_idx].add(
            contrib.reshape(-1, d))
        x_e = x_e.reshape(e_pad, cap, d)
        if ep > 1:
            if plan.a2a_fp8:
                x_e = fp8_dispatch_a2a(x_e, ep_ax, dist)
            else:
                x_e = dist.all_to_all(x_e, ep_ax, split_dim=0, concat_dim=1)
            # -> [E_loc, ep*C, D]: rows for MY experts from every EP rank

    with jax.named_scope("experts"):
        h = kops.moe_gmm(x_e, params["w_gate"], params["w_up"],
                         params["w_down"])

    with jax.named_scope("combine"):
        if ep > 1:                                          # [E, C, D]
            h = dist.all_to_all(h, ep_ax, split_dim=1, concat_dim=0)
        # gather back and combine with gates
        h_flat = h.reshape(e_pad * cap, d)
        picked = jnp.take(h_flat, flat_idx, axis=0).reshape(n_tok, -1, d)
        w = (gates * keep.astype(gates.dtype)).astype(h.dtype)
        y = jnp.einsum("tk,tkd->td", w, picked).reshape(B, t, d)

    if m.num_shared_experts:
        xs = x
        seq_sharded = plan.seq_axis is not None and dist.size(plan.seq_axis) > 1
        if seq_sharded:
            xs = dist.all_gather(xs, plan.seq_axis, dim=1)
        g = jax.nn.silu((xs @ params["w_shared_gate"]).astype(jnp.float32)).astype(xs.dtype)
        sh = (g * (xs @ params["w_shared_up"])) @ params["w_shared_down"]
        if seq_sharded:
            sh = dist.reduce_scatter(sh, plan.seq_axis, dim=1)
        else:
            sh = dist.psum(sh, plan.tp_axis)
        y = y + sh

    aux = aux_load_balance_loss(probs, idx, m.num_experts) if collect_aux else jnp.float32(0)
    return y, aux
