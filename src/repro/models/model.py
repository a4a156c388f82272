"""Model API: init / train loss / prefill / decode + spec builders.

Every step function is written in manual-SPMD style against a ``Dist``; the
launch layer wraps them in shard_map (real mesh) or calls them directly
(NullDist, single device).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeCell
from repro.models import transformer as tf
from repro.models.layers import attention as attn_mod
from repro.models.layers import common
from repro.sharding.dist import Dist, NullDist
from repro.sharding.plans import ShardingPlan


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_model(cfg: ModelConfig, plan: ShardingPlan, key):
    k_embed, k_stack, k_enc, k_norm = jax.random.split(key, 4)
    params: Dict[str, Any] = {}
    specs: Dict[str, Any] = {}
    params["embed"], specs["embed"] = common.init_embedding(cfg, plan, k_embed)
    params["stack"], specs["stack"] = tf.init_stack(
        cfg, plan, k_stack, cross=cfg.is_encoder_decoder)
    params["final_norm"], specs["final_norm"] = common.init_rms_norm(
        cfg.d_model, jnp.float32)
    if cfg.is_encoder_decoder:
        from repro.configs.base import LayerSpec
        enc_period = (LayerSpec(mixer="attn", ffn="dense"),)
        params["encoder"], specs["encoder"] = tf.init_stack(
            cfg, plan, k_enc, cross=False, n_layers=cfg.encoder_layers,
            period=enc_period)
        params["enc_norm"], specs["enc_norm"] = common.init_rms_norm(
            cfg.d_model, jnp.float32)
    # FSDP over non-stack leaves (stack leaves handled in init_layer)
    for k in ("embed", "final_norm", "enc_norm"):
        if k in params:
            specs[k] = jax.tree.map(
                lambda p, s: common.fsdp_spec(p.shape, s, plan),
                params[k], specs[k])
    return params, specs


# ---------------------------------------------------------------------------
# shared forward pieces
# ---------------------------------------------------------------------------

def _embed_inputs(params, batch, cfg, plan, dist):
    """Returns x [B, S_loc, D] from tokens (+ frontend stub embeddings)."""
    x = common.embed(params["embed"], batch["tokens"], cfg, plan, dist)
    if cfg.frontend == "vit_patches" and "patches" in batch:
        # overwrite the first n_frontend_tokens global positions with the
        # precomputed patch embeddings (replicated [B, Pf, D] input).
        patches = batch["patches"]
        B, s_loc, d = x.shape
        pf = patches.shape[1]
        r = dist.index(plan.seq_axis)
        start = r * s_loc
        padded = jnp.pad(patches, ((0, 0), (0, s_loc), (0, 0)))
        window = jax.lax.dynamic_slice(
            padded, (0, jnp.minimum(start, pf), 0), (B, s_loc, d))
        gpos = start + jnp.arange(s_loc)
        x = jnp.where((gpos < pf)[None, :, None], window.astype(x.dtype), x)
    return x


def _encode(params, frames, cfg, plan, dist, param_specs=None):
    """Audio/encoder stub path: frames [B, Se_loc, D] are already embedded."""
    from repro.configs.base import LayerSpec
    enc_period = (LayerSpec(mixer="attn", ffn="dense"),)
    x, _, _ = tf.apply_stack(
        params["encoder"], frames.astype(jnp.dtype(cfg.dtype)), cfg, plan,
        dist, mode="train", period=enc_period, n_layers=cfg.encoder_layers,
        param_specs=(param_specs or {}).get("encoder"))
    return common.rms_norm(x, params["enc_norm"]["scale"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# train forward (loss)
# ---------------------------------------------------------------------------

def train_loss(params, batch, cfg: ModelConfig, plan: ShardingPlan,
               dist: Dist, *, remat: bool = True, param_specs=None,
               unroll: bool = False):
    """batch: tokens [B, S_loc] (+ patches/frames). Global-mean LM loss."""
    if param_specs is not None and plan.fsdp_axis is not None:
        params = dict(params)
        for k in ("embed", "final_norm", "enc_norm"):
            if k in params:
                params[k] = common.fsdp_gather(params[k], param_specs[k],
                                               plan, dist)
    x = _embed_inputs(params, batch, cfg, plan, dist)
    enc_out = None
    if cfg.is_encoder_decoder:
        enc_out = _encode(params, batch["frames"], cfg, plan, dist,
                          param_specs=param_specs)
    x, _, aux = tf.apply_stack(params["stack"], x, cfg, plan, dist,
                               mode="train", collect_aux=True, remat=remat,
                               enc_out=enc_out, unroll=unroll,
                               param_specs=(param_specs or {}).get("stack"))
    x = common.rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    logits = common.lm_logits(params["embed"], x, cfg, plan, dist)

    tokens = batch["tokens"]
    B, s_loc = tokens.shape
    seq_ax = plan.seq_axis
    n_seq = dist.size(seq_ax)
    # labels = next token; the first token of the next seq shard arrives by
    # ring shift (rank n-1 receives garbage — masked as the final position).
    nxt = dist.roll(tokens[:, :1], seq_ax, shift=-1) if n_seq > 1 \
        else jnp.zeros_like(tokens[:, :1])
    labels = jnp.concatenate([tokens[:, 1:], nxt], axis=1)
    r = dist.index(seq_ax)
    gpos = r * s_loc + jnp.arange(s_loc)
    S = s_loc * n_seq
    w = (gpos < S - 1).astype(jnp.float32)[None, :]

    v_loc = logits.shape[-1]
    rv = dist.index(plan.vocab_axis)
    # max-subtraction is numerics only; its gradient path cancels exactly
    # (stop_gradient on the INPUT: pmax has no JVP rule, so it must see a
    # symbolic-zero tangent)
    m = dist.pmax(jax.lax.stop_gradient(jnp.max(logits, axis=-1)),
                  plan.vocab_axis)
    sumexp = dist.psum(jnp.sum(jnp.exp(logits - m[..., None]), axis=-1),
                       plan.vocab_axis)
    local = labels - rv * v_loc
    ok = (local >= 0) & (local < v_loc)
    safe = jnp.clip(local, 0, v_loc - 1)
    picked = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    label_logit = dist.psum(jnp.where(ok, picked, 0.0), plan.vocab_axis)
    token_loss = (jnp.log(sumexp) + m - label_logit) * w

    # global mean over every token (batch axes x sequence axis)
    reduce_axes = tuple(a for a in ((plan.batch_axes or ()) + ((seq_ax,) if seq_ax else ())) if a)
    loss_sum = jnp.sum(token_loss)
    cnt = jnp.sum(jnp.broadcast_to(w, token_loss.shape))
    for ax in reduce_axes:
        loss_sum = dist.psum(loss_sum, ax)
        cnt = dist.psum(cnt, ax)
    loss = loss_sum / jnp.maximum(cnt, 1.0)
    if cfg.moe is not None:
        aux_mean = aux / max(cfg.num_layers, 1)
        for ax in reduce_axes:
            aux_mean = dist.psum(aux_mean, ax) / dist.size(ax)
        loss = loss + cfg.moe.router_aux_loss_coef * aux_mean
    return loss


# ---------------------------------------------------------------------------
# prefill / decode
# ---------------------------------------------------------------------------

def prefill(params, batch, cfg: ModelConfig, plan: ShardingPlan, dist: Dist,
            *, unroll: bool = False):
    """Returns (next_token [B, 1], caches). Fills the KV/state caches."""
    logits, caches = prefill_logits(params, batch, cfg, plan, dist,
                                    unroll=unroll)
    return common.greedy_sample(logits, cfg, plan, dist), caches


def prefill_logits(params, batch, cfg: ModelConfig, plan: ShardingPlan,
                   dist: Dist, *, unroll: bool = False):
    """`prefill` before sampling: (logits [B, 1, V_loc] f32 of the last
    position, vocab-sharded, caches)."""
    x = _embed_inputs(params, batch, cfg, plan, dist)
    enc_out = None
    if cfg.is_encoder_decoder:
        enc_out = _encode(params, batch["frames"], cfg, plan, dist)
    x, caches, _ = tf.apply_stack(params["stack"], x, cfg, plan, dist,
                                  mode="prefill", enc_out=enc_out,
                                  unroll=unroll)
    x = common.rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    # next token comes from the LAST global position: last seq shard owns it
    seq_ax = plan.seq_axis
    n_seq = dist.size(seq_ax)
    last = x[:, -1:]
    if n_seq > 1:
        # broadcast the last rank's final hidden to every rank
        r = dist.index(seq_ax)
        contrib = jnp.where(r == n_seq - 1, last, jnp.zeros_like(last))
        last = dist.psum(contrib, seq_ax)
    return common.lm_logits(params["embed"], last, cfg, plan, dist), caches


def decode_step(params, caches, tokens, pos, cfg: ModelConfig,
                plan: ShardingPlan, dist: Dist, *, enc_len: int = 0,
                unroll: bool = False):
    """One serving step: tokens [B, 1] -> (next token [B, 1], new caches).
    pos: int32 position of each row's token in its sequence, [B]; a scalar
    gives every row the same position."""
    logits, caches = decode_logits(params, caches, tokens, pos, cfg, plan,
                                   dist, enc_len=enc_len, unroll=unroll)
    return common.greedy_sample(logits, cfg, plan, dist), caches


def decode_logits(params, caches, tokens, pos, cfg: ModelConfig,
                  plan: ShardingPlan, dist: Dist, *, enc_len: int = 0,
                  unroll: bool = False):
    """`decode_step` before sampling: (logits [B, 1, V_loc] f32,
    vocab-sharded, new caches)."""
    x = common.embed(params["embed"], tokens, cfg, plan, dist)
    x, caches, _ = tf.apply_stack(params["stack"], x, cfg, plan, dist,
                                  mode="decode", caches=caches, pos=pos,
                                  enc_len=enc_len, unroll=unroll)
    x = common.rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    return common.lm_logits(params["embed"], x, cfg, plan, dist), caches


# ---------------------------------------------------------------------------
# cache construction + specs
# ---------------------------------------------------------------------------

def _layer_cache(spec, cfg, plan: ShardingPlan, batch: int, seq: int,
                 enc_seq: int, *, cross: bool):
    """(zeros-pytree, pspec-pytree) for one layer's decode cache (GLOBAL
    shapes)."""
    dt = jnp.dtype(cfg.dtype)
    bax = plan.batch_axes
    kv_ax = plan.kv_axis
    tp = plan.tp_axis
    c, s = {}, {}
    if spec.mixer in ("attn", "attn_local"):
        if cfg.attn_kind == "mla":
            r, rp = cfg.mla_kv_lora_rank, cfg.mla_rope_head_dim
            c["mixer"] = {"c_kv": jnp.zeros((batch, seq, r), dt),
                          "k_rope": jnp.zeros((batch, seq, rp), dt)}
            s["mixer"] = {"c_kv": P(bax, None, None),
                          "k_rope": P(bax, None, None)}
        elif spec.mixer == "attn_local" and cfg.sliding_window:
            w = min(cfg.sliding_window, seq)
            kv, hd = cfg.num_kv_heads, cfg.head_dim
            c["mixer"] = {"k": jnp.zeros((batch, kv, w, hd), dt),
                          "v": jnp.zeros((batch, kv, w, hd), dt)}
            s["mixer"] = {"k": P(bax, None, None, None),
                          "v": P(bax, None, None, None)}
        else:
            kv, hd = cfg.num_kv_heads, cfg.head_dim
            c["mixer"] = {"k": jnp.zeros((batch, kv, seq, hd), dt),
                          "v": jnp.zeros((batch, kv, seq, hd), dt)}
            s["mixer"] = {"k": P(bax, None, kv_ax, None),
                          "v": P(bax, None, kv_ax, None)}
    elif spec.mixer == "mamba":
        mc = cfg.mamba
        di = mc.expand * cfg.d_model
        c["mixer"] = {"conv": jnp.zeros((batch, mc.d_conv - 1, di), dt),
                      "ssm": jnp.zeros((batch, di, mc.d_state), jnp.float32)}
        s["mixer"] = {"conv": P(bax, None, tp), "ssm": P(bax, tp, None)}
    elif spec.mixer == "rwkv":
        hd = cfg.rwkv.head_dim
        nh = cfg.d_model // hd
        c["mixer"] = {"wkv": jnp.zeros((batch, nh, hd, hd), jnp.float32),
                      "shift": jnp.zeros((batch, cfg.d_model), dt)}
        s["mixer"] = {"wkv": P(bax, tp, None, None), "shift": P(bax, None)}
        c["ffn"] = {"shift": jnp.zeros((batch, cfg.d_model), dt)}
        s["ffn"] = {"shift": P(bax, None)}
    if cross:
        kv, hd = cfg.num_kv_heads, cfg.head_dim
        c["cross"] = {"k": jnp.zeros((batch, kv, enc_seq, hd), dt),
                      "v": jnp.zeros((batch, kv, enc_seq, hd), dt)}
        s["cross"] = {"k": P(bax, None, kv_ax, None),
                      "v": P(bax, None, kv_ax, None)}
    return c, s


def init_cache(cfg: ModelConfig, plan: ShardingPlan, batch: int, seq: int,
               enc_seq: int = 0):
    """Zero-filled decode caches (GLOBAL shapes) + PartitionSpec tree."""
    period = cfg.period
    n_per = cfg.n_periods
    n_rem = cfg.n_remainder
    cross = cfg.is_encoder_decoder
    per_caches, per_specs = [], []
    for i, lspec in enumerate(period):
        c, s = _layer_cache(lspec, cfg, plan, batch, seq, enc_seq, cross=cross)
        stacked = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_per,) + x.shape), c)
        per_caches.append(stacked)
        per_specs.append(jax.tree.map(
            lambda p: P(*((None,) + tuple(p))), s,
            is_leaf=lambda p: isinstance(p, P)))
    rem_c, rem_s = [], []
    for i in range(n_rem):
        c, s = _layer_cache(period[i], cfg, plan, batch, seq, enc_seq,
                            cross=cross)
        rem_c.append(c)
        rem_s.append(s)
    caches = {"periods": tuple(per_caches), "rem": tuple(rem_c)}
    specs = {"periods": tuple(per_specs), "rem": tuple(rem_s)}
    if n_per == 0:
        caches["periods"], specs["periods"] = (), ()
    return caches, specs
