"""Continuous-batching serving engine.

A fixed pool of `max_batch` slots over a fixed-capacity cache. Requests are
admitted into free slots (prefill at the request's length, cache padded to
capacity and scattered into the slot); every decode wave advances ALL live
slots one token in one batched decode step, each slot at its own position.
Slots free as requests hit EOS or their token budget, making room for
waiting requests — the standard continuous-batching loop.

Static shapes throughout: the decode wave compiles once; prefill compiles
once per distinct prompt length, and the `engine.prefill` span's
`new_program` attr marks the call that builds (compiles, or loads from
the cache) the program for a new length.

Each step is timed where its work happens, by host spans
(`repro.serving.spans`), with their attrs:

  engine.step      one `step` (live, reads)
  engine.admit     the admission loop
  engine.prefill   one prompt, to its first token ready (rid, length,
                   new_program)
  engine.insert    the prefill into its slot, and its first token read
                   (rid)
  engine.wave      the decode wave, to its tokens ready (live)
  engine.readback  from the wave's tokens ready to the end of the step:
                   one read of the token vector, positions advanced on
                   the host, retirements (reads)

`reads` counts the device-to-host reads, each made through `_to_host`.
Slot positions live on the host (`pos`): set at admission, one added to
every row after each wave, and uploaded with the wave's call.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import model as M
from repro.serving import kvcache
from repro.serving.spans import span
from repro.sharding.dist import NullDist
from repro.sharding.plans import null_plan


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int
    generated: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


class Engine:
    """One device, unsharded: the model runs under `null_plan("decode")`
    and `NullDist()`."""

    def __init__(self, cfg: ModelConfig, params, *, max_batch: int = 4,
                 max_seq: int = 256, eos_id: int = 0):
        self.cfg = cfg
        self.params = params
        self.plan = null_plan("decode")
        self.dist = NullDist()
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.eos_id = eos_id

        enc = max_seq if cfg.is_encoder_decoder else 0
        self.caches, _ = M.init_cache(cfg, self.plan, max_batch, max_seq, enc)
        self.pos = np.zeros((max_batch,), np.int32)
        self.last_tok = jnp.zeros((max_batch, 1), jnp.int32)
        self.live = [False] * max_batch
        self.slots: List[Optional[Request]] = [None] * max_batch
        self.queue: deque[Request] = deque()
        self.finished: Dict[int, Request] = {}
        self._rid = 0
        self.host_reads = 0            # device-to-host reads so far
        self._decode_wave = self._build_decode_wave()
        self._prefill_cache: Dict[int, Any] = {}

    # ------------------------------------------------------------------
    # request lifecycle
    # ------------------------------------------------------------------

    def submit(self, prompt: List[int], max_new_tokens: int = 32) -> int:
        rid = self._rid
        self._rid += 1
        self.queue.append(Request(rid, list(prompt), max_new_tokens))
        return rid

    def _to_host(self, x) -> int | List[int]:
        """Every device-to-host read of the engine goes through here, so
        the spans' `reads` counts are exact. A scalar comes back as an
        `int`, an array as a list of its values; either is one transfer
        and counts once."""
        self.host_reads += 1
        return int(x) if x.ndim == 0 else jax.device_get(x).tolist()

    def _admit(self):
        with span("engine.admit"):
            while self.queue and not all(self.live):
                slot = self.live.index(False)
                req = self.queue.popleft()
                L = len(req.prompt)
                with span("engine.prefill", rid=req.rid, length=L,
                          new_program=int(L not in self._prefill_cache)):
                    tok0, sub = self._prefill_one(req.prompt)
                    tok0.block_until_ready()
                with span("engine.insert", rid=req.rid):
                    self.caches = kvcache.insert_slot(self.caches, sub, slot)
                    self.pos[slot] = L
                    self.last_tok = self.last_tok.at[slot].set(tok0[0])
                    req.generated = [self._to_host(tok0[0, 0])]
                self.slots[slot] = req
                self.live[slot] = True
                if req.generated[-1] == self.eos_id:
                    self._retire(slot)

    def _retire(self, slot: int):
        req = self.slots[slot]
        if req.generated and req.generated[-1] == self.eos_id:
            req.generated = req.generated[:-1]
        req.done = True
        self.finished[req.rid] = req
        self.slots[slot] = None
        self.live[slot] = False

    # ------------------------------------------------------------------
    # prefill
    # ------------------------------------------------------------------

    def _prefill_fn(self, L: int):
        """The jitted prefill for prompt length L, built once per length;
        called as fn(params, tokens[, frames])."""
        fn = self._prefill_cache.get(L)
        if fn is None:
            pplan = dataclasses.replace(self.plan, kind="prefill")

            def fn(params, tokens, frames=None):
                batch = {"tokens": tokens}
                if self.cfg.frontend == "audio_frames":
                    batch["frames"] = frames
                tok, caches = M.prefill(params, batch, self.cfg, pplan,
                                        self.dist)
                return tok, caches

            fn = jax.jit(fn)
            self._prefill_cache[L] = fn
        return fn

    def _prefill_one(self, prompt: List[int]):
        """Prefill a single request; returns (first generated token [1,1],
        capacity-padded cache with batch dim 1)."""
        L = len(prompt)
        assert 0 < L < self.max_seq, (L, self.max_seq)
        fn = self._prefill_fn(L)
        tokens = jnp.asarray(prompt, jnp.int32)[None, :]
        frames = None
        if self.cfg.frontend == "audio_frames":
            frames = jnp.zeros((1, L, self.cfg.d_model),
                               jnp.dtype(self.cfg.dtype))
        tok, sub = fn(self.params, tokens, frames) \
            if frames is not None else fn(self.params, tokens)
        sub = kvcache.pad_to_capacity(self.cfg, sub, L, self.max_seq)
        return tok, sub

    # ------------------------------------------------------------------
    # decode wave (one batched step, per-slot positions)
    # ------------------------------------------------------------------

    def _build_decode_wave(self):
        """wave(params, caches, toks [B, 1], pos [B]) -> (toks [B], caches),
        jitted with the caches donated."""
        cfg, plan, dist = self.cfg, self.plan, self.dist
        enc_len = self.max_seq if cfg.is_encoder_decoder else 0

        def wave(params, caches, toks, pos):
            # the weights are an argument, not a closed-over constant, so
            # the compiled program does not embed a copy of them
            nt, nc = M.decode_step(params, caches, toks, pos, cfg, plan,
                                   dist, enc_len=enc_len)
            return nt[:, 0], nc

        return jax.jit(wave, donate_argnums=(1,))

    def step(self) -> int:
        """One engine iteration: admit waiting requests, advance all live
        slots one token. Returns number of live slots stepped."""
        first = self.host_reads
        with span("engine.step") as step_attrs:
            self._admit()
            n_live = step_attrs["live"] = sum(self.live)
            if n_live:
                self._advance(n_live)
            step_attrs["reads"] = self.host_reads - first
        return n_live

    def _advance(self, n_live: int):
        """The decode wave over every live slot, then its token vector read
        back in one transfer, every position advanced on the host, and
        finished requests retired."""
        with span("engine.wave", live=n_live):
            toks, self.caches = self._decode_wave(self.params, self.caches,
                                                  self.last_tok, self.pos)
            toks.copy_to_host_async()
            toks.block_until_ready()
        first = self.host_reads
        with span("engine.readback") as attrs:
            self.last_tok = toks[:, None]
            # a new array, so the one handed to the wave is never written
            self.pos = self.pos + 1
            host_toks = self._to_host(toks)
            for slot, req in enumerate(self.slots):
                if req is None:
                    continue
                t = host_toks[slot]
                req.generated.append(t)
                ntok = len(req.generated) - 1   # first came from prefill
                if (t == self.eos_id or ntok >= req.max_new_tokens
                        or self.pos[slot] >= self.max_seq - 1):
                    self._retire(slot)
            attrs["reads"] = self.host_reads - first

    def run(self, max_steps: int = 10_000) -> Dict[int, List[int]]:
        """Drive until every submitted request completes."""
        for _ in range(max_steps):
            if not self.queue and not any(self.live):
                break
            self.step()
        return {rid: r.generated for rid, r in self.finished.items()}
