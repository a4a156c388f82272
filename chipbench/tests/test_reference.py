"""The plain reference against the program's prefill and cached decode,
at a tiny size on the CPU, both in float32 at highest precision."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference as R
from chipbench import weights as W
from chipbench.tests.tiny import tiny
from repro.models import model as M
from repro.serving import kvcache
from repro.sharding.dist import NullDist
from repro.sharding.plans import null_plan

SEED = 2 ** 31 + 11
PROMPT, NEW = 20, 6


def program_logits(cfg, params, prompt, n):
    """Prefill, then n-1 cached decode steps fed the greedy tokens:
    (served tokens, logits [n, V] over the real vocabulary)."""
    pplan, dplan, dist = null_plan("prefill"), null_plan("decode"), NullDist()
    with jax.default_matmul_precision("highest"):
        lg, caches = M.prefill_logits(params, {"tokens": jnp.asarray(
            [prompt], jnp.int32)}, cfg, pplan, dist)
        caches = kvcache.pad_to_capacity(cfg, caches, len(prompt), 64)
        rows = [lg[0, 0]]
        for i in range(n - 1):
            tok = jnp.argmax(rows[-1]).astype(jnp.int32).reshape(1, 1)
            lg, caches = M.decode_logits(params, caches, tok,
                                         jnp.int32(len(prompt) + i), cfg,
                                         dplan, dist)
            rows.append(lg[0, 0])
    rows = np.asarray(jnp.stack(rows))[:, :cfg.vocab_size]
    return rows.argmax(-1).tolist(), rows


def build(cfg, c, seed=SEED):
    like = jax.eval_shape(lambda k: M.init_model(cfg, null_plan("decode"),
                                                 k)[0], jax.random.PRNGKey(0))
    return W.program_params(c, seed, like)


@pytest.mark.parametrize("name,cf", [("olmoe-1b-7b", 1.0),
                                     ("granite-moe-3b-a800m", 1.0),
                                     ("granite-moe-3b-a800m", 1.5)])
def test_reference_matches_program(name, cf):
    cfg, c = tiny(name, cf=cf)
    params = build(cfg, c)
    prompt = np.random.default_rng(0).integers(1, cfg.vocab_size,
                                               PROMPT).tolist()
    served, want = program_logits(cfg, params, prompt, NEW)
    got = R.Reference(c, SEED).logits([(prompt, served)])["f32"][0]
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    assert np.all(R.served_gaps(got, served) < 1e-3)


def test_capacity_drops_tokens_at_tiny_size():
    """The comparison above covers dropping: at capacity factor 1 some
    expert of the 20-token prompt is over its capacity of 5."""
    cfg, c = tiny("olmoe-1b-7b", cf=1.0)
    assert R.capacity(PROMPT, 2, 8, 1.0) == 5
    x = jax.random.normal(jax.random.PRNGKey(3), (PROMPT, cfg.d_model))
    w = R._f32(W.layer_weights(c, W.seed_key(SEED), 0))
    probs = jax.nn.softmax(R._rmsnorm(x, w["mlp_norm"], 1e-5) @ w["router"])
    _, idx = jax.lax.top_k(probs, 2)
    assert np.bincount(np.asarray(idx).ravel(), minlength=8).max() > 5


def test_wrong_weights_fail():
    """Another seed's weights put the program's tokens far below the
    reference's best."""
    cfg, c = tiny("granite-moe-3b-a800m")
    params = build(cfg, c, seed=1)
    prompt = list(range(1, PROMPT + 1))
    served, _ = program_logits(cfg, params, prompt, NEW)
    got = R.Reference(c, 2).logits([(prompt, served)])["f32"][0]
    assert R.served_gaps(got, served).max() > 1.0


def test_layers_made_alike_stacked_and_alone():
    """The program's stacked layers and the reference's one-at-a-time
    layers hold the same numbers, bit for bit."""
    cfg, c = tiny("olmoe-1b-7b", dtype="bfloat16", layers=3)
    params = build(cfg, c)
    stacked = params["stack"]["periods"][0]["ffn"]["w_down"]
    for layer in range(3):
        alone = W.layer_weights(c, W.seed_key(SEED), layer)["w_down"]
        assert np.array_equal(np.asarray(stacked[layer], np.float32),
                              np.asarray(alone, np.float32))


def test_fp8_control_differs():
    cfg, c = tiny("olmoe-1b-7b")
    prompt = list(range(3, 3 + PROMPT))
    served = [5] * NEW
    out = R.Reference(c, SEED).logits([(prompt, served)], ("f32", "fp8"))
    d = np.abs(out["f32"][0] - out["fp8"][0]).max()
    assert 1e-3 < d < 10
