"""Serving runtime tests: engine continuous batching, DBO step equivalence,
and the speculative-decoding greedy-equivalence property."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_arch, reduced_config
from repro.models import model as M
from repro.serving import kvcache
from repro.serving.dbo import dbo_decode_step
from repro.serving.engine import Engine
from repro.serving.specdec import SDDecoder
from repro.sharding.dist import NullDist
from repro.sharding.plans import null_plan

ARCHS_FAST = ["starcoder2-3b", "olmoe-1b-7b"]
ARCHS_STATEFUL = ["rwkv6-1.6b", "jamba-v0.1-52b", "gemma3-1b"]


def make_model(arch, seed=0):
    cfg = reduced_config(get_arch(arch))
    params, _ = M.init_model(cfg, null_plan("decode"), jax.random.PRNGKey(seed))
    return cfg, params


def greedy_reference(cfg, params, prompt, n_tokens, max_seq):
    """Plain sequential greedy decode (the oracle for SD equivalence)."""
    plan, dist = null_plan("decode"), NullDist()
    pplan = null_plan("prefill")
    tok, caches = M.prefill(params, {"tokens": prompt}, cfg, pplan, dist)
    caches = kvcache.pad_to_capacity(cfg, caches, prompt.shape[1], max_seq)
    toks = [tok]
    pos = prompt.shape[1]
    for i in range(n_tokens - 1):
        tok, caches = M.decode_step(params, caches, tok, jnp.int32(pos),
                                    cfg, plan, dist)
        toks.append(tok)
        pos += 1
    return jnp.concatenate(toks, axis=1)


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ARCHS_FAST + ["rwkv6-1.6b"])
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


def test_engine_continuous_batching():
    """More requests than slots: all complete, slots are reused."""
    cfg, params = make_model("starcoder2-3b")
    eng = Engine(cfg, params, max_batch=2, max_seq=48, eos_id=-1)
    rids = [eng.submit([1 + i, 2 + i, 3 + i], max_new_tokens=4)
            for i in range(5)]
    out = eng.run()
    assert set(out) == set(rids)
    for r in rids:
        assert len(out[r]) == 5        # 1 prefill token + 4 decode tokens


def test_engine_isolation():
    """Requests decoded together must not affect each other: run the same
    prompt alone and next to a different prompt."""
    cfg, params = make_model("olmoe-1b-7b")
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


@pytest.fixture(scope="module")
def refill_reference():
    """olmoe (reduced) and each of REFILL_REQUESTS's greedy tokens, ended
    by its budget or at the last position of the cache."""
    cfg, params = make_model("olmoe-1b-7b")
    refs = []
    for prompt, budget in REFILL_REQUESTS:
        n = 1 + min(budget, REFILL_MAX_SEQ - 1 - len(prompt))
        ref = greedy_reference(cfg, params, jnp.asarray([prompt], jnp.int32),
                               n, REFILL_MAX_SEQ)
        refs.append([int(t) for t in ref[0]])
    return cfg, params, refs


@pytest.mark.parametrize("max_batch", [1, 2, 4])
def test_engine_refills_slots_with_host_positions(refill_reference,
                                                  max_batch):
    """More requests than slots, with mixed budgets and two that run to
    the end of the cache: slots refill mid-run, every request is served
    the greedy reference's tokens and ends where it does, and each live
    slot's host-held position is its prompt length plus its decoded
    tokens."""
    cfg, params, refs = refill_reference
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
# DBO step
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["olmoe-1b-7b", "jamba-v0.1-52b"])
def test_dbo_step_equivalent_to_plain(arch):
    """The interleaved DBO step must produce the same tokens/caches as two
    independent plain decode steps (it only re-orders independent work)."""
    cfg, params = make_model(arch)
    plan, dist = null_plan("decode"), NullDist()
    B, S = 2, 32
    caches_a, _ = M.init_cache(cfg, plan, B, S)
    caches_b, _ = M.init_cache(cfg, plan, B, S)
    ta = jnp.array([[3], [5]], jnp.int32)
    tb = jnp.array([[7], [9]], jnp.int32)
    pos = jnp.int32(0)

    na, ca, _ = *M.decode_step(params, caches_a, ta, pos, cfg, plan, dist), None
    nb, cb, _ = *M.decode_step(params, caches_b, tb, pos, cfg, plan, dist), None
    da, db, dca, dcb = dbo_decode_step(params, caches_a, caches_b, ta, tb,
                                       pos, cfg, plan, dist)
    assert (da == na).all() and (db == nb).all()
    for x, y in zip(jax.tree.leaves(dca), jax.tree.leaves(ca)):
        assert jnp.allclose(x, y, atol=1e-5), "microbatch A cache diverged"
    for x, y in zip(jax.tree.leaves(dcb), jax.tree.leaves(cb)):
        assert jnp.allclose(x, y, atol=1e-5), "microbatch B cache diverged"


# ---------------------------------------------------------------------------
# speculative decoding: THE invariant — SD == greedy, any draft
# ---------------------------------------------------------------------------

def _sd_vs_greedy(arch, draft_fn, n_tokens=8, seed=0):
    cfg, params = make_model(arch, seed)
    plan, dist = null_plan("decode"), NullDist()
    max_seq = 64
    prompt = jnp.asarray([[3, 5, 7, 11, 2, 4]], jnp.int32)
    ref = greedy_reference(cfg, params, prompt, n_tokens, max_seq)

    tok, caches = M.prefill(params, {"tokens": prompt}, cfg,
                            null_plan("prefill"), dist)
    caches = kvcache.pad_to_capacity(cfg, caches, prompt.shape[1], max_seq)
    dec = SDDecoder(cfg, params, spec_m=4, draft_fn=draft_fn)
    toks, _, stats = dec.generate(caches, tok, prompt.shape[1], n_tokens - 1)
    got = jnp.concatenate([tok, toks], axis=1)
    assert (got[:, :n_tokens] == ref).all(), (
        f"{arch}: SD diverged from greedy: {got} vs {ref}")
    return stats


def bad_draft(params, caches, cur_tok, pos):
    """Adversarial draft: constant garbage -> acceptance must just be 1."""
    return jnp.full((cur_tok.shape[0], 3), 12345 % 500, jnp.int32)


@pytest.mark.parametrize("arch", ARCHS_FAST + ARCHS_STATEFUL)
def test_sd_equals_greedy_bad_draft(arch):
    stats = _sd_vs_greedy(arch, bad_draft)
    assert stats["mean_accepted"] >= 1.0


@pytest.mark.parametrize("arch", ARCHS_FAST + ARCHS_STATEFUL)
def test_sd_equals_greedy_medusa_heads(arch):
    """Untrained Medusa heads (arbitrary draft quality) — output must STILL
    equal greedy; this exercises partial-acceptance rollback paths."""
    _sd_vs_greedy(arch, None)


def test_sd_perfect_draft_accepts_all():
    """Oracle draft (the model's own continuation) -> every iteration
    accepts spec_m tokens."""
    arch = "starcoder2-3b"
    cfg, params = make_model(arch)
    plan, dist = null_plan("decode"), NullDist()
    max_seq = 64
    prompt = jnp.asarray([[3, 5, 7, 11, 2, 4]], jnp.int32)
    n_tokens = 9
    ref = greedy_reference(cfg, params, prompt, n_tokens + 1, max_seq)

    # oracle: look up the reference continuation by position
    def oracle(params_, caches_, cur_tok, pos):
        del params_, caches_
        # cur_tok is ref[pos - prompt_len]; draft the next 3
        i = pos - prompt.shape[1]
        return jax.lax.dynamic_slice(ref, (0, i + 1), (1, 3))

    # oracle needs concrete pos: drive manually
    tok, caches = M.prefill(params, {"tokens": prompt}, cfg,
                            null_plan("prefill"), dist)
    caches = kvcache.pad_to_capacity(cfg, caches, prompt.shape[1], max_seq)
    dec = SDDecoder(cfg, params, spec_m=4)
    pos = prompt.shape[1]
    got = [tok]
    n_acc_all = []
    cur = tok
    while sum(t.shape[1] for t in got) < n_tokens:
        d = oracle(None, None, cur, pos)
        toks, n_acc, caches = dec._step(params, caches, cur, d,
                                        jnp.int32(pos))
        k = int(n_acc[0])
        n_acc_all.append(k)
        got.append(toks[:, :k])
        cur = toks[:, k - 1:k]
        pos += k
    seq = jnp.concatenate(got, axis=1)[:, :n_tokens]
    assert (seq == ref[:, :n_tokens]).all()
    assert all(k == 4 for k in n_acc_all[:-1]), n_acc_all


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
