"""The one traffic generator. A traffic mix is a data file,
`chipbench/traffic/<name>.json`, read by `load_mix`:

  arrival          "backlog" (every request is there at the start, and the
                   queue is kept deeper than the slots) or "poisson" (open
                   loop at `rate_rps`)
  rate_rps         for the open loop
  prompt_classes   {tokens: weight}
  output_classes   {tokens: weight}: tokens a request is served, the first
                   from its prefill
  block            requests per block (see below)
  depth            backlog only: the queue holds this many times the slots

The unit-rate arrival stream, scaled by the rate, and the Poisson gaps
come from `repro.core.traffic.generate_trace`, copied here so
that the yardstick cannot move with the program. Unlike it, prompt and
output lengths are drawn independently, and every seed gets the same
work: requests come in blocks of `block`, and each block holds every
class in its exact share and the same set of gaps, which the seed only
puts in another order. The seed also draws every prompt's token ids.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, Iterator, List

import numpy as np

GAP_SET_SEED = 20240917      # the fixed draw of each block's set of gaps


@dataclass(frozen=True)
class Request:
    index: int
    arrival_s: float          # after the window opens; 0 for a backlog
    prompt: tuple
    output_len: int


def load_mix(path) -> dict:
    mix = json.loads(Path(path).read_text())
    if mix["arrival"] not in ("backlog", "poisson"):
        raise ValueError(f"unknown arrival {mix['arrival']!r} in {path}")
    for k in ("prompt_classes", "output_classes"):
        mix[k] = {int(t): float(w) for t, w in mix[k].items()}
        if not mix[k] or min(mix[k]) < 1 or min(mix[k].values()) <= 0:
            raise ValueError(f"{k} needs positive lengths and weights")
    block = int(mix["block"])
    for k in ("prompt_classes", "output_classes"):
        _exact_counts(mix[k], block)
    return mix


def _exact_counts(classes: Dict[int, float], block: int) -> List[int]:
    """Each class's count in a block; the weights must divide it exactly."""
    total = sum(classes.values())
    counts = [w / total * block for w in classes.values()]
    if any(abs(n - round(n)) > 1e-9 for n in counts):
        raise ValueError(f"block {block} does not hold the shares "
                         f"{classes} exactly")
    return [int(round(n)) for n in counts]


def _unit_gaps(n: int) -> np.ndarray:
    """The fixed set of n gaps (generate_trace's draws), scaled to a mean
    of exactly 1 so that every block spans the same time at the rate."""
    g = np.random.default_rng(GAP_SET_SEED).exponential(1.0, size=n)
    return g / g.mean()


def _class_block(classes: Dict[int, float], block: int) -> np.ndarray:
    counts = _exact_counts(classes, block)
    return np.repeat(np.array(list(classes), np.int64), counts)


def stream(mix: dict, seed: int, vocab: int) -> Iterator[Request]:
    """The endless request stream of one seed, block by block."""
    rng = np.random.default_rng(int(seed) % (1 << 64))
    block = int(mix["block"])
    open_loop = mix["arrival"] != "backlog"
    gaps = _unit_gaps(block) / float(mix["rate_rps"]) if open_loop \
        else None
    prompts = _class_block(mix["prompt_classes"], block)
    outputs = _class_block(mix["output_classes"], block)
    t, i = 0.0, 0
    while True:
        p, o = rng.permutation(prompts), rng.permutation(outputs)
        g = rng.permutation(gaps) if open_loop else np.zeros(block)
        for j in range(block):
            t += float(g[j])
            ids = rng.integers(1, vocab, int(p[j]))
            yield Request(i, t, tuple(int(x) for x in ids), int(o[j]))
            i += 1


def take(mix: dict, seed: int, vocab: int, n: int) -> List[Request]:
    it = stream(mix, seed, vocab)
    return [next(it) for _ in range(n)]
