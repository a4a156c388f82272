"""The serving engine's per-layer metrics, read from the program's own
spans: a traced backlog run at a tiny size on the CPU reports both, and
the reads per wave come out as the engine's reads imply."""
import jax

from chipbench import cells, run, serve
from chipbench.tests.test_run import BACKLOG, SEED, tiny_cell

ENGINE_METRICS = [m for m in cells.load_benchmark()["per_layer"]
                  if m["name"] in ("readback_ms.backlog",
                                   "host_reads_per_wave.backlog")]


def test_traced_backlog_run_reports_the_engine_metrics(monkeypatch):
    cfg, cell = tiny_cell(BACKLOG)
    cell.per_layer = ENGINE_METRICS
    seen = {}
    serve_window = serve.serve_window
    array_type = type(jax.numpy.zeros(()))
    to_int = array_type.__int__

    def counting(x):
        seen["ints"] += 1
        return to_int(x)

    def window(rec, *args, **kw):
        """The window as the harness serves it, with every `int` of a
        device array counted and the waves, prefills and requests kept."""
        seen["ints"] = 0
        monkeypatch.setattr(array_type, "__int__", counting)
        try:
            out = serve_window(rec, *args, **kw)
        finally:
            monkeypatch.setattr(array_type, "__int__", to_int)
        seen.update(window=out, waves=list(rec.waves),
                    prefills=list(rec.prefills), reqs=dict(rec.reqs))
        return out

    monkeypatch.setattr(serve, "serve_window", window)
    out = run.run_cell(cell, SEED, 1.5, True, cfg=cfg,
                       t_start=run.serve.clock())
    assert out["correct"], out["checks"]
    got = out["metrics"]
    assert set(got) == {m["name"] for m in ENGINE_METRICS}
    assert got["readback_ms.backlog"]["unit"] == "ms"
    assert got["readback_ms.backlog"]["value"] > 0

    t_open, t_close = seen["window"]
    waves = seen["waves"]
    assert waves and all(t_open < b <= t_close for _, b, _ in waves)
    per_wave = got["host_reads_per_wave.backlog"]["value"]
    assert per_wave == seen["ints"] / len(waves)
    # two reads per live slot per wave, one per admission, less one per
    # request its budget retired (every request here: no EOS, room to spare)
    slots = cell.sizing["slots"]
    admitted = len(seen["prefills"])
    retired = sum(len(r.tokens) == r.output_len
                  for r in seen["reqs"].values())
    assert all(n == slots for _, _, n in waves)
    assert per_wave == (2 * slots * len(waves) + admitted - retired) \
        / len(waves)
