"""Plain float32 reference for the decoders with sparse experts.

It follows the published layer equations, with the departures that the
configuration file lists:

  x = embed[tokens] * embedding_multiplier
  per layer:
    h = rmsnorm(x) * attn_norm
    q, k, v = h wq, h wk, h wv; rotary embedding on q and k (halves
      rotated, theta = rope_theta); key/value head j serves query heads
      j*g .. j*g+g-1 (g = H / KV)
    o = softmax(q k^T * attention_multiplier, causal) v
    x = x + (o wo) * residual_multiplier
    h = rmsnorm(x) * mlp_norm
    p = softmax(h router); top-k of p, renormalised to sum 1 where
      norm_topk_prob holds
    an expert keeps at most capacity(n) = ceil(n * k * capacity_factor / E)
      of the routing decisions of each group of n tokens, taken token by
      token, in the order of the top-k; the rest are dropped. A prompt is
      one group; each later token is a group of its own.
    x = x + sum over kept experts of gate * w_down(silu(h w_gate) * h w_up)
      * residual_multiplier
  logits = (rmsnorm(x) * final_norm) head / logits_scaling

There are no kernels, no cache and no batching: every position of a
sequence is computed again from its tokens, and the experts are applied
densely, each to every token, with the gates of the dropped and the
unchosen ones zero. Matrix products run at float32 `highest` precision.

`mode="fp8"` is the control, the reference computed one step below the
program's bfloat16: every weight and activation that enters a projection
or an expert is rounded to float8 e4m3 (a scale per row of activations
and per column of weights), with float32 accumulation, and every tensor
that the program holds in bfloat16 between operations (the residual
stream, norm outputs, q, k, v, attention probabilities and output,
expert activations and outputs) is held in float8. The router and the
output head stay float32.

The weights are made again from the seed (`weights.py`), one layer at a
time, so only one layer's float32 weights are on the device at once.
Nothing of the program is imported.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W

HI = jax.lax.Precision.HIGHEST
BUCKET = 256                 # sequences are padded to a multiple of this
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
MODES = ("f32", "fp8")


def capacity(n: int, k: int, n_exp: int, cf: float) -> int:
    """Static expert capacity of a group of n tokens (GShard's rule)."""
    return max(int(-(-n * k * cf // n_exp)), 1)


def _q8(x, axis):
    """Round to float8 e4m3 with one scale per slice along `axis`."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(a, b, mode):
    """a [..., K] @ b [K, N] in float32 at highest precision; in fp8 mode
    both operands are rounded first (a per row, b per column)."""
    if mode == "fp8":
        a, b = _q8(a, -1), _q8(b, 0)
    return jnp.matmul(a, b, precision=HI)


def _held(x, mode):
    """A tensor as the computation holds it between operations."""
    return _q8(x, -1) if mode == "fp8" else x


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x [T, heads, hd]; the two halves of each head rotate together."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def scalars(c: dict) -> dict:
    m = W.dims(c)
    return dict(
        eps=float(c["rms_norm_eps"]), theta=float(c["rope_theta"]),
        attn=float(c.get("attention_multiplier", m["hd"] ** -0.5)),
        emb=float(c.get("embedding_multiplier", 1.0)),
        res=float(c.get("residual_multiplier", 1.0)),
        logit_div=float(c.get("logits_scaling", 1.0)),
        renorm=bool(c.get("norm_topk_prob", True)),
        cf=float(c["capacity_factor"]))


def _layer(w, x, valid, group_start, cap, *, m, s, mode):
    """One layer over one padded sequence. x [T, D] f32; valid [T] bool;
    group_start [T] (first token of each token's group); cap [T]."""
    T = x.shape[0]
    H, KV, hd, E, k = m["H"], m["KV"], m["hd"], m["E"], m["k"]
    pos = jnp.arange(T)
    st = functools.partial(_held, mode=mode)
    x = st(x)
    h = st(_rmsnorm(x, w["attn_norm"], s["eps"]))
    q = st(_rope(_mm(h, w["wq"], mode).reshape(T, H, hd), pos, s["theta"]))
    kk = st(_rope(_mm(h, w["wk"], mode).reshape(T, KV, hd), pos,
                  s["theta"]))
    v = st(_mm(h, w["wv"], mode).reshape(T, KV, hd))
    g = H // KV
    kk, v = jnp.repeat(kk, g, axis=1), jnp.repeat(v, g, axis=1)
    sc = jnp.einsum("thd,shd->hts", q, kk, precision=HI) * s["attn"]
    mask = (pos[None, :] <= pos[:, None]) & valid[None, :]
    sc = jnp.where(mask[None], sc, -jnp.inf)
    p = st(jax.nn.softmax(sc, axis=-1))
    o = st(jnp.einsum("hts,shd->thd", p, v, precision=HI).reshape(T, H * hd))
    x = st(x + _mm(o, w["wo"], mode) * s["res"])

    h = st(_rmsnorm(x, w["mlp_norm"], s["eps"]))
    probs = jax.nn.softmax(jnp.matmul(h, w["router"], precision=HI), -1)
    gates, idx = jax.lax.top_k(probs, k)
    if s["renorm"]:
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)              # [T, k, E]
    flat = onehot.reshape(T * k, E)
    before = jnp.cumsum(flat, 0) - flat         # earlier decisions per expert
    at_group = before[group_start * k]          # [T, E] before the group
    slot = jnp.sum((before.reshape(T, k, E) - at_group[:, None]) * onehot, -1)
    keep = (slot < cap[:, None]) & valid[:, None]
    comb = jnp.einsum("tk,tke->te", jnp.where(keep, gates, 0.0),
                      onehot.astype(jnp.float32))                 # [T, E]

    def expert(y, e):
        wg, wu, wd, ce = e
        a = st(jax.nn.silu(_mm(h, wg, mode)) * _mm(h, wu, mode))
        return y + ce[:, None] * st(_mm(a, wd, mode)), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        (w["w_gate"], w["w_up"], w["w_down"], comb.T))
    return st(x + y * s["res"])


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


class Reference:
    """The reference for one configuration and seed.

    `logits(seqs, modes)` runs it over sequences given as (prompt, served)
    token lists: the input is the prompt and every served token but the
    last, and the result holds, per mode and sequence, the logits [n, V]
    at the n positions that predicted the served tokens."""

    def __init__(self, c: dict, seed: int):
        self.c, self.seed = c, seed
        self.m, self.s = W.dims(c), scalars(c)
        # the key is an argument, not a constant of the program, so that
        # every seed finds the compiled programs in the cache
        self._key = W.seed_key(seed)
        self._layer_w = jax.jit(lambda k, l: _f32(W.layer_weights(c, k, l)))
        self._top_w = jax.jit(lambda k: _f32(W.top_weights(c, k)))
        self._layer_fns = {
            mode: jax.jit(functools.partial(_layer, m=self.m, s=self.s,
                                            mode=mode))
            for mode in MODES}

    def _inputs(self, prompt: Sequence[int], served: Sequence[int]):
        toks = list(prompt) + list(served[:-1])
        n_in, L = len(toks), len(prompt)
        T = -(-n_in // BUCKET) * BUCKET
        m, cf = self.m, self.s["cf"]
        gs = np.arange(T, dtype=np.int32)
        gs[:L] = 0
        cap = np.full(T, capacity(1, m["k"], m["E"], cf), np.int32)
        cap[:L] = capacity(L, m["k"], m["E"], cf)
        valid = np.arange(T) < n_in
        ids = np.zeros(T, np.int32)
        ids[:n_in] = toks
        return (jnp.asarray(ids), jnp.asarray(valid), jnp.asarray(gs),
                jnp.asarray(cap), L - 1, len(served))

    def logits(self, seqs: List[Tuple[Sequence[int], Sequence[int]]],
               modes=("f32",)) -> Dict[str, List[np.ndarray]]:
        inp = [self._inputs(p, sv) for p, sv in seqs]
        top = self._top_w(self._key)
        xs = {mode: [top["embed"][ids] * self.s["emb"] for ids, *_ in inp]
              for mode in modes}
        for layer in range(self.m["L"]):
            w = self._layer_w(self._key, jnp.int32(layer))
            for mode in modes:
                fn = self._layer_fns[mode]
                xs[mode] = [fn(w, x, valid, gs, cap) for x, (_, valid, gs, cap,
                            _, _) in zip(xs[mode], inp)]
            del w
        head = top["embed"].T if self.m["tied"] else top["head"]
        out = {}
        for mode in modes:
            rows = []
            for x, (_, _, _, _, first, n) in zip(xs[mode], inp):
                h = _held(_rmsnorm(x[first:first + n], top["final_norm"],
                                   self.s["eps"]), mode)
                rows.append(np.asarray(
                    jnp.matmul(h, head, precision=HI) / self.s["logit_div"]))
            out[mode] = rows
        return out


def served_gaps(ref_logits: np.ndarray, served: Sequence[int]) -> np.ndarray:
    """How far below the reference's best logit each served token's lies."""
    rows = np.arange(len(served))
    return ref_logits.max(-1) - ref_logits[rows, np.asarray(served)]


def choice_gaps(ref_logits: np.ndarray, other_logits: np.ndarray) -> np.ndarray:
    """The gap, in the reference, of the token another computation ranks
    first at each position."""
    return served_gaps(ref_logits, other_logits.argmax(-1))

