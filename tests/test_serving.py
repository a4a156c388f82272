"""Serving runtime tests: the continuous-batching engine against plain
greedy decoding, over attention, MoE, sliding-window and recurrent
(mamba, rwkv) layers."""
import functools

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_arch, reduced_config
from repro.models import model as M
from repro.serving import kvcache
from repro.serving.engine import Engine
from repro.sharding.dist import NullDist
from repro.sharding.plans import null_plan

ARCHS_FAST = ["starcoder2-3b", "olmoe-1b-7b"]
ARCHS_STATEFUL = ["rwkv6-1.6b", "jamba-v0.1-52b", "gemma3-1b"]
ARCHS = ARCHS_FAST + ARCHS_STATEFUL


def make_model(arch, seed=0):
    cfg = reduced_config(get_arch(arch))
    params, _ = M.init_model(cfg, null_plan("decode"), jax.random.PRNGKey(seed))
    return cfg, params


@functools.cache
def _greedy_steps(cfg):
    """Jitted batch-1 prefill and decode step for `cfg` (one compile per
    prompt length and one for the step, shared by every request)."""
    plan, dist = null_plan("decode"), NullDist()
    pplan = null_plan("prefill")
    prefill = jax.jit(lambda p, t: M.prefill(p, {"tokens": t}, cfg, pplan,
                                             dist))
    step = jax.jit(lambda p, c, t, pos: M.decode_step(p, c, t, pos, cfg,
                                                      plan, dist))
    return prefill, step


def greedy_reference(cfg, params, prompt, n_tokens, max_seq):
    """Plain sequential greedy decode, one request at a batch of one with a
    scalar position (the oracle for the engine)."""
    prefill, step = _greedy_steps(cfg)
    tok, caches = prefill(params, prompt)
    caches = kvcache.pad_to_capacity(cfg, caches, prompt.shape[1], max_seq)
    toks = [tok]
    pos = prompt.shape[1]
    for i in range(n_tokens - 1):
        tok, caches = step(params, caches, tok, jnp.int32(pos))
        toks.append(tok)
        pos += 1
    return jnp.concatenate(toks, axis=1)


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ARCHS)
def test_engine_matches_sequential(arch):
    """Engine output for a single request == plain greedy decode."""
    cfg, params = make_model(arch)
    prompt = [3, 5, 7, 11, 2, 4]
    ref = greedy_reference(cfg, params,
                           jnp.asarray(prompt, jnp.int32)[None], 6, 64)
    eng = Engine(cfg, params, max_batch=2, max_seq=64, eos_id=-1)
    rid = eng.submit(prompt, max_new_tokens=6)
    out = eng.run()
    assert out[rid][:6] == [int(t) for t in ref[0][:6]]


@pytest.mark.parametrize("arch", ARCHS)
def test_engine_continuous_batching(arch):
    """More requests than slots: all complete, slots are reused."""
    cfg, params = make_model(arch)
    eng = Engine(cfg, params, max_batch=2, max_seq=48, eos_id=-1)
    rids = [eng.submit([1 + i, 2 + i, 3 + i], max_new_tokens=4)
            for i in range(5)]
    out = eng.run()
    assert set(out) == set(rids)
    for r in rids:
        assert len(out[r]) == 5        # 1 prefill token + 4 decode tokens


@pytest.mark.parametrize("arch", ARCHS)
def test_engine_isolation(arch):
    """Requests decoded together must not affect each other: run the same
    prompt alone and next to a different prompt."""
    cfg, params = make_model(arch)
    p1, p2 = [3, 1, 4, 1, 5], [9, 2, 6, 5, 3]
    eng1 = Engine(cfg, params, max_batch=2, max_seq=48, eos_id=-1)
    r1 = eng1.submit(p1, max_new_tokens=5)
    alone = eng1.run()[r1]
    eng2 = Engine(cfg, params, max_batch=2, max_seq=48, eos_id=-1)
    ra = eng2.submit(p1, max_new_tokens=5)
    eng2.submit(p2, max_new_tokens=5)
    both = eng2.run()
    assert both[ra] == alone


REFILL_REQUESTS = [([3, 1, 4, 1, 5], 2), ([9, 2, 6], 4),
                   ([5, 3, 5, 8, 9, 7], 1), ([2, 7, 1, 8], 40),
                   ([8, 2, 8], 3), ([1, 6, 1, 8, 0, 3, 3], 5)]
REFILL_MAX_SEQ = 10


@functools.cache
def refill_reference(arch):
    """The model (reduced) and each of REFILL_REQUESTS's greedy tokens,
    ended by its budget or at the last position of the cache."""
    cfg, params = make_model(arch)
    refs = []
    for prompt, budget in REFILL_REQUESTS:
        n = 1 + min(budget, REFILL_MAX_SEQ - 1 - len(prompt))
        ref = greedy_reference(cfg, params, jnp.asarray([prompt], jnp.int32),
                               n, REFILL_MAX_SEQ)
        refs.append([int(t) for t in ref[0]])
    return cfg, params, refs


@pytest.mark.parametrize("arch,max_batch", [
    pytest.param("olmoe-1b-7b", 1, id="1"),
    pytest.param("olmoe-1b-7b", 2, id="2"),
    pytest.param("olmoe-1b-7b", 4, id="4"),
    ("rwkv6-1.6b", 2),
    ("jamba-v0.1-52b", 2),
])
def test_engine_refills_slots_with_host_positions(arch, max_batch):
    """More requests than slots, with mixed budgets and two that run to
    the end of the cache: slots refill mid-run, every request is served
    the greedy reference's tokens and ends where it does, and each live
    slot's host-held position is its prompt length plus its decoded
    tokens. In the recurrent models (rwkv, mamba) a refilled slot must
    start from its new prompt's state, not the state its last request
    left behind."""
    cfg, params, refs = refill_reference(arch)
    eng = Engine(cfg, params, max_batch=max_batch, max_seq=REFILL_MAX_SEQ,
                 eos_id=-1)
    rids = [eng.submit(p, max_new_tokens=b) for p, b in REFILL_REQUESTS]
    while eng.queue or any(eng.live):
        eng.step()
        for slot, req in enumerate(eng.slots):
            if req is not None:
                assert eng.pos[slot] == \
                    len(req.prompt) + len(req.generated) - 1
    assert set(eng.finished) == set(rids)
    for rid, ref in zip(rids, refs):
        assert eng.finished[rid].generated == ref


# ---------------------------------------------------------------------------
# kvcache utilities
# ---------------------------------------------------------------------------

def test_classify_and_pad():
    cfg = reduced_config(get_arch("jamba-v0.1-52b"))
    plan = null_plan("decode")
    caches, _ = M.init_cache(cfg, plan, 2, 16)
    classes = kvcache.classify(cfg, caches)
    vals = set(jax.tree.leaves(classes))
    assert vals == {"positional", "recurrent"}
    padded = kvcache.pad_to_capacity(cfg, caches, 16, 32)
    # attention k/v grew; mamba states untouched
    k_leaves = [x for x in jax.tree.leaves(padded) if x.ndim >= 4]
    assert any(x.shape[-2] == 32 for x in k_leaves)
    assert kvcache.memory_bytes(padded) > kvcache.memory_bytes(caches)
