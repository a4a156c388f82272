"""A whole run of a cell at a tiny size on the CPU, past the harness's
look for a chip: the program serves, the reference judges. A sound
program comes out correct; the timed path broken underneath comes out
not correct, once for each fault a one-chip serving cell can have."""
import jax
import jax.numpy as jnp
import pytest

from chipbench import cells, run
from chipbench.tests.tiny import tiny
from repro.serving import engine as engine_mod

SEED = 2 ** 31 + 977
MIX = {"arrival": "poisson", "rate_rps": 40.0, "block": 10,
       "prompt_classes": {12: 0.5, 20: 0.5}, "output_classes": {4: 0.6, 9: 0.4}}
BACKLOG = {"arrival": "backlog", "depth": 2, "block": 10,
           "prompt_classes": {12: 0.5, 20: 0.5}, "output_classes": {6: 1.0}}


def tiny_cell(mix, name="granite-moe-3b-a800m"):
    cfg, c = tiny(name, cf=1.5)
    cell = cells.Cell(
        name=f"tiny.{mix['arrival']}", entry={"chips": 1}, config=c,
        sizing={"slots": 4, "positions": 64,
                "check": {"tokens": 10 ** 6, "min_tokens": 8,
                          "mean_gap": 0.005}},
        mix=mix, end_to_end=[], per_layer=[])
    return cfg, cell


def go(mix, seconds=1.5):
    cfg, cell = tiny_cell(mix)
    return run.run_cell(cell, SEED, seconds, False, cfg=cfg,
                        t_start=run.serve.clock())


@pytest.mark.parametrize("mix", [MIX, BACKLOG], ids=["open", "backlog"])
def test_sound_run_is_correct(mix):
    out = go(mix)
    assert out["correct"], out["checks"]
    assert out["checks"]["tokens_compared"]["value"] >= 8
    assert out["_notes"]["compiles_in_window"] == 0
    assert out["device"]["platform"] == "cpu"


def _broken_wave(fault):
    build = engine_mod.Engine._build_decode_wave

    def patched(self):
        wave = build(self)

        def broken(params, caches, toks, pos):
            # the cache as it went in (copied: the wave donates its input)
            kept = jax.tree.map(jnp.copy, caches)
            new_toks, new_caches = wave(params, caches, toks, pos)
            if fault == "state_unchanged":
                return new_toks, kept
            if fault == "half_batch":
                half = toks.shape[0] // 2
                return new_toks.at[half:].set(toks[half:, 0]), new_caches
            if fault == "token_altered":
                return new_toks.at[0].set((new_toks[0] + 1)
                                          % self.cfg.vocab_size), new_caches
            raise ValueError(fault)

        return broken

    return patched


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered"])
def test_broken_path_is_not_correct(fault, monkeypatch):
    monkeypatch.setattr(engine_mod.Engine, "_build_decode_wave",
                        _broken_wave(fault))
    out = go(BACKLOG)
    assert not out["correct"], out["checks"]
    assert out["checks"]["mean_gap"]["value"] > \
        out["checks"]["mean_gap"]["limit"]


def test_fp8_control_is_not_correct():
    """The reference computed in fp8, put in the program's place, reads
    above the limit that the sound program reads under."""
    cfg, cell = tiny_cell(BACKLOG)
    out = run.run_cell(cell, SEED, 1.0, False, cfg=cfg,
                       t_start=run.serve.clock(), control=True)
    limit = out["checks"]["mean_gap"]["limit"]
    assert out["correct"] and out["checks"]["mean_gap"]["value"] < limit
    assert not out["_notes"]["control_correct"]
    assert out["_notes"]["control_gaps"]["mean_gap"] > limit


def test_rate_sweep_follows_the_offered_rate():
    """The knee sweep serves each rate in turn from one engine: below
    capacity every request due is admitted."""
    from chipbench import sweep
    cfg, cell = tiny_cell(MIX)
    rows = list(sweep.sweep(cell, SEED, 1.0, [5.0, 10.0], cfg=cfg))
    assert [r["rate_rps"] for r in rows] == [5.0, 10.0]
    for r in rows:
        assert r["due"] >= 1 and r["ttft_p90_ms"] > 0
