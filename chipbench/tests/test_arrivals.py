"""The traffic generator: a seed gives its trace exactly again, and every
seed gets the same work in another order."""
import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from chipbench import arrivals

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"
SEED = 2 ** 31 + 5


@pytest.mark.parametrize("name", sorted(p.stem for p in TRAFFIC.glob("*.json")))
def test_seed_reproduces_trace(name):
    mix = arrivals.load_mix(TRAFFIC / f"{name}.json")
    a = arrivals.take(mix, SEED, 1000, 250)
    b = arrivals.take(mix, SEED, 1000, 250)
    assert a == b
    c = arrivals.take(mix, SEED + 1, 1000, 250)
    assert a != c


@pytest.mark.parametrize("name", sorted(p.stem for p in TRAFFIC.glob("*.json")))
def test_every_seed_gets_the_same_work(name):
    mix = arrivals.load_mix(TRAFFIC / f"{name}.json")
    n = int(mix["block"])
    sets = []
    for seed in (0, 1, SEED):
        reqs = arrivals.take(mix, seed, 1000, n)
        sets.append((Counter(len(r.prompt) for r in reqs),
                     Counter(r.output_len for r in reqs),
                     round(reqs[-1].arrival_s, 9)))
    assert sets[0] == sets[1] == sets[2]
    counts = sets[0][0]
    total = sum(mix["prompt_classes"].values())
    for length, w in mix["prompt_classes"].items():
        assert counts[length] == round(w / total * n)


def test_open_loop_rate(tmp_path):
    p = tmp_path / "open.json"
    p.write_text(json.dumps({"arrival": "poisson", "rate_rps": 4.0,
                             "block": 100, "prompt_classes": {"512": 0.3,
                                                              "4096": 0.7},
                             "output_classes": {"4": 0.5, "16": 0.5}}))
    mix = arrivals.load_mix(p)
    reqs = arrivals.take(mix, SEED, 1000, 3 * int(mix["block"]))
    t = np.array([r.arrival_s for r in reqs])
    assert np.all(np.diff(t) >= 0)
    assert len(t) / t[-1] == pytest.approx(mix["rate_rps"], rel=1e-9)


def test_backlog_arrives_at_once():
    mix = arrivals.load_mix(TRAFFIC / "decode_backlog.json")
    assert all(r.arrival_s == 0 for r in arrivals.take(mix, 3, 1000, 50))


def test_block_must_hold_the_shares(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text(json.dumps({"arrival": "poisson", "rate_rps": 1.0,
                             "block": 7, "prompt_classes": {"8": 0.5,
                                                            "16": 0.5},
                             "output_classes": {"4": 1.0}}))
    with pytest.raises(ValueError):
        arrivals.load_mix(p)
