"""Whether what the timed path served is correct.

Once the window has closed and the program's state is freed, a sample of
the requests served in the window, drawn from the seed, goes through the
configuration's plain reference: each prompt with the tokens it was
served by the close (`Reference.logits`). Every served token is a greedy
choice of the program, so its gap below the reference's best logit at
its position is a rounding difference where the program is sound, and
some logits wide where it is not.

Over the sample's gaps (`stats`): the widest (`max_gap`), the mean
(`mean_gap`) and the share of served tokens that are not the reference's
best (`off_argmax`). Each one that the cell's `check` block in its
workload file gives a limit is held to it (`judge`).

The sample holds the request with the most served tokens and the one
with the longest prompt, then others in the seed's order until it holds
`check.tokens` served tokens.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

STATS = ("max_gap", "mean_gap", "off_argmax")


def sample(served: Dict[int, List[int]], prompts: Dict[int, Sequence[int]],
           seed: int, tokens: int) -> List[int]:
    rids = sorted(r for r in served if served[r])
    if not rids:
        return []
    most = max(rids, key=lambda r: (len(served[r]), len(prompts[r]), -r))
    longest = max(rids, key=lambda r: (len(prompts[r]), len(served[r]), -r))
    picked = [most] + ([longest] if longest != most else [])
    rest = [r for r in rids if r not in picked]
    rng = np.random.default_rng(int(seed) % (1 << 64) ^ 0xC4EC)
    for r in rng.permutation(np.array(rest, dtype=np.int64)).tolist():
        if sum(len(served[p]) for p in picked) >= tokens:
            break
        picked.append(int(r))
    return picked


def gaps(ref, seqs: List[Tuple[Sequence[int], Sequence[int]]],
         reference_module, control: bool = False
         ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """(the gap of every served token below the reference's best, and with
    `control` the gap of the token the fp8 control ranks first at each of
    the same positions, else None)."""
    logits = ref.logits(seqs, ("f32", "fp8") if control else ("f32",))

    def cat(rows):
        return np.concatenate(rows) if rows else np.zeros(0)

    prog = cat([reference_module.served_gaps(lg, sv)
                for lg, (_, sv) in zip(logits["f32"], seqs)])
    ctrl = cat([reference_module.choice_gaps(a, b)
                for a, b in zip(logits["f32"], logits["fp8"])]) \
        if control else None
    return prog, ctrl


def stats(g: np.ndarray) -> Dict[str, float]:
    if not g.size:
        return {k: float("nan") for k in STATS}
    return {"max_gap": float(g.max()), "mean_gap": float(g.mean()),
            "off_argmax": float(np.mean(g > 0))}


def judge(st: Dict[str, float], n_tokens: int, n_lost: int, chk: dict):
    """(correct, checks): each number compared, beside its limit. The gap
    statistics that `chk` limits and the requests lost may not exceed
    their limits; the tokens compared may not fall below theirs."""
    checks = {k: {"value": st[k], "limit": chk[k]} for k in STATS if k in chk}
    ok = all(np.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in checks.values())
    checks["tokens_compared"] = {"value": n_tokens, "limit": chk["min_tokens"]}
    checks["requests_lost"] = {"value": n_lost, "limit": 0}
    ok = ok and n_tokens >= chk["min_tokens"] and n_lost == 0
    return bool(ok), checks
