"""Drives `repro.serving.engine.Engine` through a cell's traffic and keeps
what the metrics read: when each request arrived, when its prefill ran,
and when each of its tokens reached the host.

The engine is timed from outside: `Recorder` wraps three of its methods
on the instance (nothing of the program is edited). The wrappers wait for
the device's result (`block_until_ready`) before they read the clock,
which the engine does itself a moment later when it reads the tokens back
with `int(...)`. With tracing on they also write host spans into the
profiler's trace (`jax.profiler.TraceAnnotation`):

  chipbench.window   the measured window
  chipbench.step     one `Engine.step` (admission, the wave, retirement)
  chipbench.admit    `Engine._admit`: prefills of waiting requests
  chipbench.prefill  `Engine._prefill_one`: one prompt, to its first token
  chipbench.wave     `Engine._decode_wave`: every slot one token
  chipbench.submit   the harness handing arrived requests to the engine
  chipbench.wait     the harness waiting for the next arrival
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import numpy as np

from chipbench import arrivals

clock = time.perf_counter


@dataclass
class Req:
    rid: int
    arrival: float             # scheduled, on the host clock
    prompt: tuple
    output_len: int
    prefill: Optional[tuple] = None            # (start, token on the host)
    tokens: List[float] = field(default_factory=list)   # host times


class Recorder:
    def __init__(self, eng, trace: bool):
        self.eng = eng
        self.trace = trace
        self.reqs: Dict[int, Req] = {}
        self.by_prompt: Dict[int, int] = {}
        self.prefills: List[tuple] = []        # (start, end, prompt length)
        self.waves: List[tuple] = []           # (start, end, live slots)
        orig_prefill, orig_wave = eng._prefill_one, eng._decode_wave
        orig_admit = eng._admit

        def prefill_one(prompt):
            rid = self.by_prompt.pop(id(prompt))
            with self.span("chipbench.prefill"):
                t0 = clock()
                tok, sub = orig_prefill(prompt)
                tok.block_until_ready()
                t1 = clock()
            self.prefills.append((t0, t1, len(prompt)))
            r = self.reqs[rid]
            r.prefill = (t0, t1)
            r.tokens.append(t1)
            return tok, sub

        def decode_wave(params, caches, toks, pos):
            live = [r.rid for r in self.eng.slots if r is not None]
            with self.span("chipbench.wave"):
                t0 = clock()
                out = orig_wave(params, caches, toks, pos)
                out[0].block_until_ready()
                t1 = clock()
            self.waves.append((t0, t1, len(live)))
            for rid in live:
                self.reqs[rid].tokens.append(t1)
            return out

        def admit():
            with self.span("chipbench.admit"):
                return orig_admit()

        eng._prefill_one, eng._decode_wave = prefill_one, decode_wave
        eng._admit = admit

    def span(self, name: str):
        return jax.profiler.TraceAnnotation(name) if self.trace \
            else contextlib.nullcontext()

    def submit(self, prompt, output_len: int, arrival: float) -> Req:
        """Hand one request to the engine: it is served `output_len`
        tokens, the first from its prefill."""
        rid = self.eng.submit(list(prompt), max_new_tokens=output_len - 1)
        self.by_prompt[id(self.eng.queue[-1].prompt)] = rid
        r = Req(rid, arrival, tuple(prompt), output_len)
        self.reqs[rid] = r
        return r

    def step(self):
        with self.span("chipbench.step"):
            self.eng.step()

    def forget(self):
        self.reqs.clear()
        self.prefills.clear()
        self.waves.clear()
        self.eng.finished.clear()

    def served(self) -> Dict[int, List[int]]:
        """Every request's tokens so far, finished or still in a slot."""
        out = {rid: list(r.generated) for rid, r in self.eng.finished.items()}
        for r in self.eng.slots:
            if r is not None:
                out[r.rid] = list(r.generated)
        return out


def warm_up(rec: Recorder, mix: dict, slots: int, vocab: int, seed: int):
    """Compile and load every shape the cell's traffic uses: a prefill per
    prompt length, the decode wave, and the host-side updates of every
    slot. Each slot takes a request of one token from its prefill and one
    from the wave."""
    lengths = sorted(mix["prompt_classes"])
    rng = np.random.default_rng(int(seed) % (1 << 64) ^ 0x5EED)
    for i in range(max(slots, len(lengths))):
        rec.submit(rng.integers(1, vocab, lengths[i % len(lengths)]), 2, 0.0)
    while rec.eng.queue or any(rec.eng.live):
        rec.step()
    rec.forget()


def serve_window(rec: Recorder, mix: dict, slots: int, seed: int,
                 vocab: int, seconds: float) -> tuple:
    """Serve the traffic for `seconds`; returns (open, close) on the host
    clock. A step that is running at the deadline ends the window."""
    eng = rec.eng
    reqs = arrivals.stream(mix, seed, vocab)
    nxt = next(reqs)
    backlog = mix["arrival"] == "backlog"
    depth = int(mix.get("depth", 2)) * slots
    with rec.span("chipbench.window"):
        t_open = clock()
        deadline = t_open + seconds
        while True:
            now = clock()
            if now >= deadline:
                break
            with rec.span("chipbench.submit"):
                if backlog:
                    while len(eng.queue) < depth:
                        rec.submit(nxt.prompt, nxt.output_len, t_open)
                        nxt = next(reqs)
                else:
                    while t_open + nxt.arrival_s <= now:
                        rec.submit(nxt.prompt, nxt.output_len,
                                   t_open + nxt.arrival_s)
                        nxt = next(reqs)
            if eng.queue or any(eng.live):
                rec.step()
            else:
                with rec.span("chipbench.wait"):
                    time.sleep(max(0.0, min(t_open + nxt.arrival_s,
                                            deadline) - clock()))
        t_close = clock()
    if not backlog:
        # due in the window but not yet handed over: they count as waiting
        late = -1
        while t_open + nxt.arrival_s < t_close:
            rec.reqs[late] = Req(late, t_open + nxt.arrival_s, nxt.prompt,
                                 nxt.output_len)
            late -= 1
            nxt = next(reqs)
    return t_open, t_close
