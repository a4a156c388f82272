"""Decoder stack: scan-over-periods with an unrolled remainder.

A model is `n_periods` repetitions of `cfg.period` (a tuple of LayerSpecs)
plus `n_remainder` leading pattern positions. Parameters and caches are
stored as a tuple (one tree per position-in-period) of leaves stacked over
periods, so the whole stack lowers as one `lax.scan` — keeping the HLO small
enough to GSPMD-compile 95-layer models for 512 devices.

Layer = pre-norm mixer (+ cross-attention for enc-dec) + pre-norm FFN,
residual around each.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import LayerSpec, ModelConfig
from repro.models.layers import attention as attn
from repro.models.layers import common, mamba as mamba_mod, mla as mla_mod
from repro.models.layers import moe as moe_mod, rwkv as rwkv_mod
from repro.sharding.dist import Dist
from repro.sharding.plans import ShardingPlan


# ---------------------------------------------------------------------------
# per-layer init
# ---------------------------------------------------------------------------

def init_layer(spec: LayerSpec, cfg: ModelConfig, plan: ShardingPlan, key,
               *, cross: bool = False):
    ks = jax.random.split(key, 6)
    params: Dict[str, Any] = {}
    specs: Dict[str, Any] = {}

    params["norm1"], specs["norm1"] = common.init_rms_norm(cfg.d_model, jnp.float32)
    if spec.mixer in ("attn", "attn_local"):
        if cfg.attn_kind == "mla":
            params["mixer"], specs["mixer"] = mla_mod.init_mla(cfg, plan, ks[0])
        else:
            params["mixer"], specs["mixer"] = attn.init_attention(cfg, plan, ks[0])
    elif spec.mixer == "mamba":
        params["mixer"], specs["mixer"] = mamba_mod.init_mamba(cfg, plan, ks[0])
    elif spec.mixer == "rwkv":
        params["mixer"], specs["mixer"] = rwkv_mod.init_rwkv_tm(cfg, plan, ks[0])

    if cross:
        params["norm_x"], specs["norm_x"] = common.init_rms_norm(cfg.d_model, jnp.float32)
        params["cross"], specs["cross"] = attn.init_attention(cfg, plan, ks[1])

    params["norm2"], specs["norm2"] = common.init_rms_norm(cfg.d_model, jnp.float32)
    if spec.mixer == "rwkv":
        params["ffn"], specs["ffn"] = rwkv_mod.init_rwkv_cm(cfg, plan, ks[2])
    elif spec.ffn == "dense":
        params["ffn"], specs["ffn"] = common.init_dense_ffn(cfg, plan, ks[2])
    elif spec.ffn == "moe":
        params["ffn"], specs["ffn"] = moe_mod.init_moe(cfg, plan, ks[2])
    # FSDP (training): extend specs BEFORE period-stacking so the scan dim is
    # never sharded; forward all-gathers per period (common.fsdp_gather).
    specs = jax.tree.map(lambda p, s: common.fsdp_spec(p.shape, s, plan),
                         params, specs)
    return params, specs


# ---------------------------------------------------------------------------
# per-layer apply
# ---------------------------------------------------------------------------

def apply_layer(spec: LayerSpec, p, x, cfg, plan: ShardingPlan, dist: Dist, *,
                mode: str, cache=None, pos=None, enc_len=None, enc_out=None,
                collect_aux: bool = False):
    """mode: train | prefill | decode. Returns (x, new_cache, aux).
    pos (decode): int32 position of each row's token, [B], or a scalar
    that every row shares."""
    new_cache: Dict[str, Any] = {}
    if mode == "decode":
        pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), x.shape[:1])
    aux = jnp.float32(0)
    window = cfg.sliding_window if spec.mixer == "attn_local" else 0

    h = common.rms_norm(x, p["norm1"]["scale"], cfg.norm_eps)
    if spec.mixer in ("attn", "attn_local"):
        if cfg.attn_kind == "mla":
            if mode == "decode":
                h, c = mla_mod.mla_decode(p["mixer"], h, cache["mixer"], pos,
                                          cfg, plan, dist)
            else:
                h, c = mla_mod.mla_fwd(p["mixer"], h, cfg, plan, dist,
                                       make_cache=(mode == "prefill"))
        else:
            if mode == "decode":
                h, c = attn.attention_decode(p["mixer"], h, cache["mixer"],
                                             pos, cfg, plan, dist,
                                             window=window)
            else:
                h, c = attn.attention_fwd(p["mixer"], h, cfg, plan, dist,
                                          causal=True, window=window,
                                          make_cache=(mode == "prefill"))
        if c is not None:
            new_cache["mixer"] = c
    elif spec.mixer == "mamba":
        if mode == "decode":
            h, c = mamba_mod.mamba_decode(p["mixer"], h, cache["mixer"],
                                          cfg, plan, dist)
        else:
            h, c = mamba_mod.mamba_fwd(p["mixer"], h, cfg, plan, dist,
                                       make_cache=(mode == "prefill"))
        if c is not None:
            new_cache["mixer"] = c
    elif spec.mixer == "rwkv":
        if mode == "decode":
            h, c = rwkv_mod.rwkv_tm_decode(p["mixer"], h, cache["mixer"],
                                           cfg, plan, dist)
        else:
            h, c = rwkv_mod.rwkv_tm_fwd(p["mixer"], h, cfg, plan, dist,
                                        make_cache=(mode == "prefill"))
        if c is not None:
            new_cache["mixer"] = c
    else:
        h = jnp.zeros_like(x)
    x = x + h

    if "cross" in p:
        h = common.rms_norm(x, p["norm_x"]["scale"], cfg.norm_eps)
        if mode == "decode":
            h = attn.cross_attention_decode(p["cross"], h, cache["cross"],
                                            enc_len, cfg, plan, dist)
            new_cache["cross"] = cache["cross"]      # read-only pass-through
        else:
            enc_kv = attn.make_enc_cache(p["cross"], enc_out, cfg, plan, dist)
            h = attn.cross_attention_fwd(p["cross"], h, enc_kv, cfg,
                                         plan, dist)
            if mode == "prefill":
                new_cache["cross"] = enc_kv
        x = x + h

    h = common.rms_norm(x, p["norm2"]["scale"], cfg.norm_eps)
    if spec.mixer == "rwkv":
        if mode == "decode":
            h, c = rwkv_mod.rwkv_cm_decode(p["ffn"], h, cache["ffn"], plan, dist)
        else:
            h, c = rwkv_mod.rwkv_cm_fwd(p["ffn"], h, plan, dist,
                                        make_cache=(mode == "prefill"))
        if c is not None:
            new_cache["ffn"] = c
    elif spec.ffn == "dense":
        h = common.dense_ffn(p["ffn"], h, plan, dist)
    elif spec.ffn == "moe":
        h, aux = moe_mod.moe_ffn(p["ffn"], h, cfg, plan, dist,
                                 decode=(mode == "decode"),
                                 collect_aux=collect_aux)
    else:
        h = jnp.zeros_like(x)
    x = x + h
    return x, (new_cache if new_cache else None), aux


# ---------------------------------------------------------------------------
# stack init
# ---------------------------------------------------------------------------

def _stack_trees(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def _prepend_none(spec_tree):
    return jax.tree.map(
        lambda s: P(*((None,) + tuple(s))), spec_tree,
        is_leaf=lambda s: isinstance(s, P))


def init_stack(cfg: ModelConfig, plan: ShardingPlan, key, *,
               cross: bool = False, n_layers: Optional[int] = None,
               period: Optional[Tuple[LayerSpec, ...]] = None):
    """Returns ({"periods": tuple_of_stacked, "rem": tuple}, same-shape specs)."""
    period = period or cfg.period
    n_layers = n_layers if n_layers is not None else cfg.num_layers
    n_per = n_layers // len(period)
    n_rem = n_layers % len(period)

    keys = jax.random.split(key, n_layers + 1)
    periods, rem = [], []
    spec_tree_pos = []
    for i, spec in enumerate(period):
        per_layer = [init_layer(spec, cfg, plan, keys[j * len(period) + i],
                                cross=cross)
                     for j in range(n_per)]
        ps = [p for p, _ in per_layer]
        spec_tree_pos.append(per_layer[0][1])
        periods.append(_stack_trees(ps) if n_per else None)
    rem_specs = []
    for i in range(n_rem):
        p, s = init_layer(period[i], cfg, plan, keys[n_per * len(period) + i],
                          cross=cross)
        rem.append(p)
        rem_specs.append(s)
    params = {"periods": tuple(periods), "rem": tuple(rem)}
    specs = {"periods": tuple(_prepend_none(s) for s in spec_tree_pos),
             "rem": tuple(rem_specs)}
    if n_per == 0:
        params["periods"], specs["periods"] = (), ()
    return params, specs


# ---------------------------------------------------------------------------
# stack apply
# ---------------------------------------------------------------------------

def apply_stack(params, x, cfg: ModelConfig, plan: ShardingPlan, dist: Dist,
                *, mode: str, caches=None, pos=None, enc_len=None,
                enc_out=None, collect_aux: bool = False, remat: bool = False,
                period: Optional[Tuple[LayerSpec, ...]] = None,
                n_layers: Optional[int] = None, param_specs=None,
                unroll: bool = False):
    """caches: {"periods": tuple_of_stacked, "rem": tuple} (decode) or None
    (train/prefill — prefill CREATES caches). Returns (x, new_caches|None, aux).
    pos (decode): [B] per-row positions, or a scalar shared by every row.

    unroll=True unrolls the period scan (XLA cost_analysis counts a scan
    body once, so exact roofline accounting needs the unrolled program;
    launch.dryrun --unroll)."""
    period = period or cfg.period
    n_layers = n_layers if n_layers is not None else cfg.num_layers
    n_per = n_layers // len(period)
    n_rem = n_layers % len(period)
    want_cache = mode in ("prefill", "decode")
    have_cache = caches is not None

    def one_period(x, aux, pparams, pcaches):
        new_caches = []
        for i, spec in enumerate(period):
            p_i = pparams[i]
            if param_specs is not None and plan.fsdp_axis is not None:
                # strip the leading period-dim None from the stacked spec
                sp_i = jax.tree.map(lambda s: P(*tuple(s)[1:]),
                                    param_specs["periods"][i],
                                    is_leaf=lambda s: isinstance(s, P))
                p_i = common.fsdp_gather(p_i, sp_i, plan, dist)
            c_in = pcaches[i] if pcaches is not None else None
            x, c, a = apply_layer(spec, p_i, x, cfg, plan, dist,
                                  mode=mode, cache=c_in, pos=pos,
                                  enc_len=enc_len, enc_out=enc_out,
                                  collect_aux=collect_aux)
            aux = aux + a
            new_caches.append(c)
        return x, aux, tuple(new_caches)

    aux = jnp.float32(0)
    new_period_caches = None
    if n_per > 0:
        def body(carry, xs):
            x, aux = carry
            if have_cache:
                pparams, pcaches = xs
            else:
                pparams, pcaches = xs, None
            x, aux, ncache = one_period(x, aux, pparams, pcaches)
            return (x, aux), (ncache if want_cache else None)

        scan_body = jax.checkpoint(body) if remat else body
        xs = (params["periods"], caches["periods"]) if have_cache \
            else params["periods"]
        (x, aux), ys = jax.lax.scan(scan_body, (x, aux), xs,
                                    unroll=n_per if unroll else 1)
        new_period_caches = ys if want_cache else None

    new_rem = []
    for i in range(n_rem):
        c_in = caches["rem"][i] if have_cache else None
        p_i = params["rem"][i]
        if param_specs is not None and plan.fsdp_axis is not None:
            p_i = common.fsdp_gather(p_i, param_specs["rem"][i], plan, dist)
        x, c, a = apply_layer(period[i], p_i, x, cfg, plan, dist,
                              mode=mode, cache=c_in, pos=pos, enc_len=enc_len,
                              enc_out=enc_out, collect_aux=collect_aux)
        aux = aux + a
        new_rem.append(c)

    new_caches = None
    if want_cache:
        new_caches = {"periods": new_period_caches, "rem": tuple(new_rem)}
    return x, new_caches, aux
