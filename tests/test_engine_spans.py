"""Host spans inside `Engine.step`: how they nest, what their attrs say,
the bound of the span buffer, and the engine methods that a caller may
wrap on the instance."""
import collections

import jax
import pytest

from repro.configs import get_arch, reduced_config
from repro.models import model as M
from repro.serving import spans as S
from repro.serving.engine import Engine
from repro.sharding.plans import null_plan

PARENT = {"engine.step": None, "engine.admit": "engine.step",
          "engine.wave": "engine.step", "engine.readback": "engine.step",
          "engine.prefill": "engine.admit", "engine.insert": "engine.admit"}
PROMPTS = [[3, 5, 7], [2, 4, 6, 8, 10], [9, 1, 4], [6, 6, 2, 7, 1],
           [5, 3, 8, 1, 2, 9, 4]]


@pytest.fixture(scope="module")
def model():
    cfg = reduced_config(get_arch("olmoe-1b-7b"))
    params, _ = M.init_model(cfg, null_plan("decode"), jax.random.PRNGKey(0))
    return cfg, params


def newest_id() -> int:
    return max((r.id for r in S.records()), default=-1)


def serve(model, prompts=PROMPTS, *, new_tokens=4, max_seq=48, eos_id=-1):
    """Serve `prompts` through two slots; returns (engine, rids, the span
    records this run closed)."""
    eng = Engine(*model, max_batch=2, max_seq=max_seq, eos_id=eos_id)
    rids = [eng.submit(p, max_new_tokens=new_tokens) for p in prompts]
    first = newest_id()
    eng.run()
    return eng, rids, [r for r in S.records() if r.id > first]


def test_spans_nest_as_documented(model):
    eng, rids, recs = serve(model)
    by_id = {r.id: r for r in recs}
    for r in recs:
        parent = by_id.get(r.parent)
        assert (parent.name if parent else None) == PARENT[r.name], r
        if parent:
            assert parent.start <= r.start <= r.end <= parent.end
    names = collections.Counter(r.name for r in recs)
    assert names["engine.step"] == names["engine.admit"]
    assert names["engine.wave"] == names["engine.readback"]
    assert names["engine.prefill"] == names["engine.insert"] == len(PROMPTS)
    # admission is first come, first served; each prompt's insert follows
    # its prefill under the same admission
    pre = [r for r in recs if r.name == "engine.prefill"]
    ins = [r for r in recs if r.name == "engine.insert"]
    assert [r.attrs["rid"] for r in pre] == rids
    assert [r.attrs["rid"] for r in ins] == rids
    assert [r.attrs["length"] for r in pre] == [len(p) for p in PROMPTS]
    for p, i in zip(pre, ins):
        assert p.parent == i.parent and p.end <= i.start
    steps = [r for r in recs if r.name == "engine.step"]
    waves = {r.parent: r for r in recs if r.name == "engine.wave"}
    for s in steps:
        if s.attrs["live"]:
            assert waves[s.id].attrs["live"] == s.attrs["live"]
    assert sum(s.attrs["reads"] for s in steps) == eng.host_reads


@pytest.mark.parametrize("retire_by", ["budget", "positions", "eos"])
def test_reads_equal_an_independent_count(model, retire_by, monkeypatch):
    """Every `int` or `__array__` taken of a device array is a read,
    counted here apart from the engine's own count. The engine reads one
    token at each admission (its first) and the whole token vector once a
    wave, whatever the live count: slot positions stay on the host, so
    retiring by position reads nothing."""
    kw = {"budget": {"new_tokens": 4},
          "positions": {"new_tokens": 40, "max_seq": 12},
          "eos": {"new_tokens": 6}}[retire_by]
    if retire_by == "eos":
        # a token that some wave makes and that no prefill does
        eng, rids, _ = serve(model, **kw)
        gens = [eng.finished[r].generated for r in rids]
        kw["eos_id"] = next(t for g in gens for t in g[1:]
                            if t not in {g[0] for g in gens})
    eng = Engine(*model, max_batch=2, max_seq=kw.get("max_seq", 48),
                 eos_id=kw.get("eos_id", -1))
    rids = [eng.submit(p, max_new_tokens=kw["new_tokens"]) for p in PROMPTS]
    by_token = collections.Counter()
    orig_retire = eng._retire

    def retire(slot):
        req = eng.slots[slot]
        if len(req.generated) > 1 and (
                req.generated[-1] == eng.eos_id
                or len(req.generated) - 1 >= req.max_new_tokens):
            by_token["eos" if req.generated[-1] == eng.eos_id
                     else "budget"] += 1
        orig_retire(slot)

    eng._retire = retire
    counted = []
    array_type = type(jax.numpy.zeros(()))

    def counting(method):
        def read(x, *args, **kwargs):
            counted.append(method.__name__)
            return method(x, *args, **kwargs)
        return read

    first = newest_id()
    for name in ("__int__", "__array__"):
        monkeypatch.setattr(array_type, name,
                            counting(getattr(array_type, name)))
    eng.run()
    monkeypatch.undo()
    recs = [r for r in S.records() if r.id > first]

    steps = [r for r in recs if r.name == "engine.step"]
    assert sum(s.attrs["reads"] for s in steps) == len(counted) \
        == eng.host_reads
    assert set(eng.finished) == set(rids)
    if retire_by == "positions":
        assert not by_token
    else:
        assert by_token[retire_by] > 0
    waves = [r for r in recs if r.name == "engine.wave"]
    readbacks = [r for r in recs if r.name == "engine.readback"]
    assert eng.host_reads == len(waves) + len(rids)
    assert len(readbacks) == len(waves)
    assert all(r.attrs["reads"] == 1 for r in readbacks)


def test_new_program_only_on_first_prefill_of_each_length(model):
    prompts = [[1, 2, 3], [4, 5, 6, 7, 8], [9, 8, 7], [6, 5, 4, 3, 2],
               [1, 1, 2, 3, 5, 8, 13]]
    _, _, recs = serve(model, prompts, new_tokens=2)
    pre = [r.attrs for r in recs if r.name == "engine.prefill"]
    assert [a["length"] for a in pre] == [3, 5, 3, 5, 7]
    assert [a["new_program"] for a in pre] == [1, 1, 0, 0, 1]


def test_buffer_is_bounded_and_keeps_the_newest():
    extra = 10
    for i in range(S.MAX_RECORDS + extra):
        with S.span("test.fill", i=i):
            pass
    recs = S.records()
    assert len(recs) == S.MAX_RECORDS
    assert [r.attrs["i"] for r in recs[-3:]] == [
        S.MAX_RECORDS + extra - 3, S.MAX_RECORDS + extra - 2,
        S.MAX_RECORDS + extra - 1]
    assert recs[0].attrs["i"] == extra
    assert [r.id for r in recs] == list(range(recs[0].id,
                                              recs[0].id + S.MAX_RECORDS))


def test_span_keeps_late_attrs_and_closes_on_error():
    with pytest.raises(ValueError):
        with S.span("test.outer", a=1) as attrs:
            attrs["b"] = 2
            with S.span("test.inner"):
                raise ValueError("inside")
    inner, outer = S.records()[-2:]
    assert (inner.name, outer.name) == ("test.inner", "test.outer")
    assert inner.parent == outer.id and outer.parent is None
    assert outer.attrs == {"a": 1, "b": 2}
    assert outer.start <= inner.start <= inner.end <= outer.end


def test_instance_patched_methods_see_every_prefill_and_wave(model):
    """A caller may wrap `_prefill_one`, `_decode_wave` and `_admit` on the
    instance: the engine calls each through `self`, hands `_prefill_one`
    the request's own prompt list, and keeps its signatures."""
    eng = Engine(*model, max_batch=2, max_seq=48, eos_id=-1)
    seen = collections.Counter()
    prompts_seen = []
    orig_prefill, orig_wave = eng._prefill_one, eng._decode_wave
    orig_admit = eng._admit

    def prefill_one(prompt):
        seen["prefill"] += 1
        prompts_seen.append(prompt)
        tok, sub = orig_prefill(prompt)
        assert tok.shape == (1, 1)
        return tok, sub

    def decode_wave(params, caches, toks, pos):
        seen["wave"] += 1
        out = orig_wave(params, caches, toks, pos)
        assert len(out) == 2 and out[0].shape == (eng.max_batch,)
        return out

    def admit():
        seen["admit"] += 1
        return orig_admit()

    eng._prefill_one, eng._decode_wave = prefill_one, decode_wave
    eng._admit = admit
    rids = [eng.submit(p, max_new_tokens=3) for p in PROMPTS]
    queued = [r.prompt for r in eng.queue]
    first = newest_id()
    out = eng.run()
    recs = collections.Counter(r.name for r in S.records() if r.id > first)
    assert set(out) == set(rids)
    assert all(a is b for a, b in zip(prompts_seen, queued))
    assert seen["prefill"] == recs["engine.prefill"] == len(PROMPTS)
    assert seen["wave"] == recs["engine.wave"] > 0
    assert seen["admit"] == recs["engine.admit"] == recs["engine.step"]


def test_spans_reach_the_profiler_trace(model, tmp_path):
    """In a profiler trace the engine's spans are host events, with their
    attrs as stats, the late ones included."""
    from jax.profiler import ProfileData
    eng = Engine(*model, max_batch=2, max_seq=48, eos_id=-1)
    eng.submit(PROMPTS[0], max_new_tokens=2)
    eng.step()                                 # compiled before the trace
    eng.submit(PROMPTS[2], max_new_tokens=2)
    with jax.profiler.trace(str(tmp_path)):
        eng.run()
    path = next(tmp_path.rglob("*.xplane.pb"))
    events = [ev for plane in ProfileData.from_file(str(path)).planes
              if plane.name.startswith("/host")
              for line in plane.lines for ev in line.events
              if ev.name.startswith("engine.")]
    names = {ev.name for ev in events}
    assert set(PARENT) <= names
    stats = {k for ev in events if ev.name == "engine.step"
             for k, _ in ev.stats}
    assert {"live", "reads"} <= stats
