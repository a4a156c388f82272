"""One batched decode step over rows at different positions.

The engine advances every slot in one `decode_step` with a vector of
per-slot positions. Each row must come out as if it were decoded alone:
its rope, cache write and mask at its own position, and its expert rows
never shared with another row's token.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_arch, reduced_config
from repro.models import model as M
from repro.models.layers import moe
from repro.serving import kvcache
from repro.sharding.dist import NullDist
from repro.sharding.plans import null_plan

MAX_SEQ = 32
LENGTHS = (5, 11, 17)        # past the reduced sliding window (8) for two rows
# bf16 weights and activations: a few units of bf16's 2^-8 at logits of O(1)
TOL = 2e-2

CASES = {
    "full-attention": ("olmoe-1b-7b", False),
    "sliding-window": ("gemma3-1b", False),
    "mla": ("deepseek-v3", False),
    "recurrent": ("rwkv6-1.6b", False),
    "mamba": ("jamba-v0.1-52b", False),
    "dead-slot": ("olmoe-1b-7b", True),
}


@pytest.mark.parametrize("case", list(CASES))
def test_decode_step_rows_match_rows_alone(case):
    """B=3 rows at three positions, two steps, against three batch-1 calls
    with the same tokens. In the dead-slot case the last row sits at
    MAX_SEQ - 1 and steps past the cache's end; the live rows must not
    notice it."""
    arch, dead = CASES[case]
    cfg = reduced_config(get_arch(arch))
    plan, dist = null_plan("decode"), NullDist()
    params, _ = M.init_model(cfg, plan, jax.random.PRNGKey(0))
    logits_of = jax.jit(lambda p, c, t, pos: M.decode_logits(
        p, c, t, pos, cfg, plan, dist))

    caches, _ = M.init_cache(cfg, plan, len(LENGTHS), MAX_SEQ)
    alone, toks = [], []
    for b, n in enumerate(LENGTHS):
        prompt = jax.random.randint(jax.random.PRNGKey(10 + b), (1, n), 1,
                                    cfg.vocab_size)
        tok, sub = M.prefill(params, {"tokens": prompt}, cfg,
                             null_plan("prefill"), dist)
        sub = kvcache.pad_to_capacity(cfg, sub, n, MAX_SEQ)
        caches = kvcache.insert_slot(caches, sub, b)
        alone.append(sub)
        toks.append(tok)
    toks = jnp.concatenate(toks, axis=0)                      # [3, 1]
    pos = jnp.asarray(LENGTHS, jnp.int32)
    live = range(len(LENGTHS))
    if dead:
        pos = pos.at[-1].set(MAX_SEQ - 1)
        live = range(len(LENGTHS) - 1)

    for _ in range(2):
        lg, caches = logits_of(params, caches, toks, pos)
        for b in live:
            lg_b, alone[b] = logits_of(params, alone[b], toks[b:b + 1],
                                       pos[b])
            assert jnp.allclose(lg[b], lg_b[0], atol=TOL, rtol=TOL), (
                case, b, float(jnp.abs(lg[b] - lg_b[0]).max()))
        toks = jnp.argmax(lg[:, :, :cfg.vocab_size], axis=-1).astype(
            jnp.int32)
        pos = pos + 1


def test_moe_decode_capacity_keeps_every_decision():
    """Every one of B decode tokens is routed to the same two experts. In
    decode each expert holds B rows, so all B x k decisions are kept and
    each token's output is the one it gets alone; the prompt rule (one
    group at the capacity factor) would drop the tokens past its
    capacity."""
    base = reduced_config(get_arch("olmoe-1b-7b"))
    cfg = base.replace(moe=dataclasses.replace(base.moe, experts_per_token=2))
    m = cfg.moe
    plan, dist = null_plan("decode"), NullDist()
    params, _ = moe.init_moe(cfg, plan, jax.random.PRNGKey(0))
    # positive inputs against two positive router columns: experts 2 and 5
    # win for every token, with gates that differ from token to token
    params["router"] = (jnp.zeros_like(params["router"])
                        .at[:, 2].set(1.0).at[:, 5].set(0.5))
    B = 8
    x = (jnp.abs(jax.random.normal(jax.random.PRNGKey(1),
                                   (B, 1, cfg.d_model))) + 0.1
         ).astype(jnp.dtype(cfg.dtype))
    _, idx, _ = moe.route(x[:, 0].astype(jnp.float32) @ params["router"],
                          m.experts_per_token, m.num_experts)
    assert (jnp.sort(idx, axis=1) == jnp.array([2, 5])).all()

    y, _ = moe.moe_ffn(params, x, cfg, plan, dist, decode=True)
    each = jnp.concatenate([
        moe.moe_ffn(params, x[b:b + 1], cfg, plan, dist, decode=True)[0]
        for b in range(B)], axis=0)
    assert (jnp.abs(each) > 0).any(axis=-1).all()
    assert jnp.allclose(y, each, atol=TOL, rtol=TOL), float(
        jnp.abs(y - each).max())

    cap = moe.capacity(B, m.experts_per_token, params["router"].shape[-1],
                       m.capacity_factor)
    assert cap < B
    y_group, _ = moe.moe_ffn(params, x, cfg, plan, dist)
    assert (y_group[cap:] == 0).all()
