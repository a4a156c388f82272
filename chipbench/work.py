"""Operations and bytes the served tokens need, from the configuration's
sizes alone (never from a kernel's padded shapes), for the MFU and
roofline metrics. A FLOP is one multiply or one add; a multiply-add is 2.
"""
from __future__ import annotations

from chipbench.weights import dims

BF16 = 2


def active_params(c: dict) -> int:
    """Weights one token multiplies by: attention projections, router, its
    k experts, and the output head (the embedding is a lookup)."""
    m = dims(c)
    D, H, KV, hd, k, F, V = (m[x] for x in ("D", "H", "KV", "hd", "k", "F",
                                             "V"))
    attn = D * H * hd * 2 + D * KV * hd * 2
    per_layer = attn + D * m["E"] + k * 3 * D * F
    return m["L"] * per_layer + D * V


def attention_flops(c: dict, context: int) -> int:
    """Scores and weighted values of one token against `context` positions
    (its own included), over every layer."""
    m = dims(c)
    return m["L"] * 4 * m["H"] * m["hd"] * context


def decode_token_flops(c: dict, context: int) -> int:
    """One decoded token at a cache of `context` positions."""
    return 2 * active_params(c) + attention_flops(c, context)


def prefill_flops(c: dict, n: int) -> int:
    """A prompt of n tokens, causal: token i attends to i + 1 positions."""
    return 2 * active_params(c) * n + attention_flops(c, n * (n + 1) // 2)


def distinct_experts(n_exp: int, k: int, tokens: int) -> float:
    """Expected number of experts that T tokens, each routed uniformly to
    k of E, touch: E * (1 - (1 - k/E)^T)."""
    return n_exp * (1.0 - (1.0 - k / n_exp) ** tokens)


def gmm_flops(c: dict, tokens: int) -> int:
    """One grouped-matmul call (one layer) for `tokens` tokens: each of
    its k routed rows goes through three D x F products."""
    m = dims(c)
    return 6 * m["D"] * m["F"] * tokens * m["k"]


def gmm_bytes(c: dict, tokens: int) -> float:
    """One grouped-matmul call: the weights of every expert touched, read
    once, plus the routed rows read and written, in bf16."""
    m = dims(c)
    weights = distinct_experts(m["E"], m["k"], tokens) * 3 * m["D"] * m["F"]
    rows = 2 * tokens * m["k"] * m["D"]
    return (weights + rows) * BF16


def gmm_least_seconds(c: dict, tokens: int, peak: dict) -> float:
    """The least time the chip could take for one call: the larger of its
    operations over peak FLOP/s and its bytes over peak bandwidth."""
    return max(gmm_flops(c, tokens) / peak["bf16_flops"],
               gmm_bytes(c, tokens) / peak["hbm_bytes_s"])
