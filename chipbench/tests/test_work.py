"""The FLOP and byte counts behind the MFU and roofline metrics."""
import math

import pytest

from chipbench import peaks, work
from chipbench.tests.tiny import tiny

OLMOE = {"num_hidden_layers": 16, "hidden_size": 2048,
         "num_attention_heads": 16, "num_key_value_heads": 16, "head_dim": 128,
         "num_experts": 64, "num_experts_per_tok": 8, "intermediate_size": 1024,
         "vocab_size": 50304, "tie_word_embeddings": False,
         "torch_dtype": "bfloat16"}


def test_active_params_olmoe():
    attn = 4 * 2048 * 2048
    per_layer = attn + 2048 * 64 + 8 * 3 * 2048 * 1024
    assert work.active_params(OLMOE) == 16 * per_layer + 2048 * 50304
    # OLMoE-1B-7B: 1.28 B active parameters with the embedding counted,
    # which is a lookup here
    assert work.active_params(OLMOE) + 2048 * 50304 == pytest.approx(
        1.28e9, rel=0.01)


def test_decode_and_prefill_flops():
    a = work.active_params(OLMOE)
    att = 16 * 4 * 16 * 128
    assert work.decode_token_flops(OLMOE, 100) == 2 * a + att * 100
    assert work.prefill_flops(OLMOE, 3) == 2 * a * 3 + att * 6


@pytest.mark.parametrize("e,k,t", [(64, 8, 1), (64, 8, 8), (40, 8, 32),
                                   (8, 2, 5)])
def test_distinct_experts(e, k, t):
    got = work.distinct_experts(e, k, t)
    assert got == pytest.approx(e * (1 - (1 - k / e) ** t))
    if t == 1:
        assert got == pytest.approx(k)
    assert k <= got + 1e-9 and got <= e


def test_distinct_experts_matches_sampling():
    import numpy as np
    rng = np.random.default_rng(0)
    e, k, t = 64, 8, 8
    seen = [len({x for _ in range(t) for x in rng.choice(e, k, replace=False)})
            for _ in range(4000)]
    assert np.mean(seen) == pytest.approx(work.distinct_experts(e, k, t),
                                          rel=0.01)


def test_gmm_counts():
    # one layer of an 8-token olmoe wave: ~42 of 64 experts touched
    flops = work.gmm_flops(OLMOE, 8)
    assert flops == 6 * 2048 * 1024 * 8 * 8
    n = work.distinct_experts(64, 8, 8)
    assert 41 < n < 43
    bytes_ = work.gmm_bytes(OLMOE, 8)
    assert bytes_ == pytest.approx((n * 3 * 2048 * 1024 + 2 * 8 * 8 * 2048) * 2)
    p = peaks.peaks("TPU v5 lite")
    least = work.gmm_least_seconds(OLMOE, 8, p)
    assert least == pytest.approx(bytes_ / 819e9)      # bandwidth bound
    # a 4096-token prefill's call is bound by the MXU instead
    assert work.gmm_least_seconds(OLMOE, 4096, p) == pytest.approx(
        work.gmm_flops(OLMOE, 4096) / 197e12)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12


def test_tiny_config_counts():
    _, c = tiny("granite-moe-3b-a800m")
    assert work.active_params(c) > 0
    assert math.isfinite(work.gmm_bytes(c, 3))
