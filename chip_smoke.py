#!/usr/bin/env python3
"""Bring-up check: the sharded MoE serving steps on four TPU chips.

  python3 chip_smoke.py

The `launch.steps` prefill and decode on a (data=1, model=4) mesh of one
host's four chips: olmoe-1b-7b at its published width (d_model 2048, 64
experts top-8, d_expert 1024, vocab 50304), cut to four layers, with
random weights made from a seed and initialised under `jax.jit`. The
experts are split over the chips, and one weight tree serves both
phases. The decode program must hold the expert all-to-all, and the
one-chip NullDist model must agree that every sharded token, fed the
reference's tokens, is its greedy choice. (Serving on one chip is what
the benchmark measures: `chipbench/run.py`.)

"Agree" allows a near tie. Two programs that batch or shard the same
step round differently: on a v5e a 4-slot decode wave and a 1-slot one
gave logits up to 0.148 apart for the same slot, so greedy decoding can
part where the top two logits are that close. A token passes when the
reference's logit for it is within GREEDY_TOL of the reference's best;
a wrong slot, cache or shard gives a token some 3-4 logits below it.

There is no CPU fallback: without a TPU the script exits non-zero and
prints no result. The last line of standard output is one JSON object,
{"ok": true, "device": {...}}. JAX's compilation cache is kept where
JAX_COMPILATION_CACHE_DIR says, and otherwise in <repo>/.jax_cache.
"""
from __future__ import annotations

import collections
import dataclasses
import json
import os
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_arch  # noqa: E402
from repro.configs.base import ShapeCell  # noqa: E402
from repro.launch import steps  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.serving import kvcache  # noqa: E402
from repro.sharding.dist import NullDist  # noqa: E402
from repro.sharding.plans import make_plan, null_plan  # noqa: E402

ARCH = "olmoe-1b-7b"
SEED = 0
# depth cut so the one-chip reference fits beside its shard
FOUR_CHIP_LAYERS = 4
FOUR_CHIP_MESH = (("data", "model"), (1, 4))
FOUR_CHIP_BATCH, FOUR_CHIP_PROMPT, FOUR_CHIP_NEW = 4, 64, 16
FOUR_CHIP_SEQ = 512
# how far below the reference's greedy logit a token may lie and still
# count as the same greedy choice: above the 0.148 rounding gap measured
# between the wave and a 1-slot program, far below a wrong token's 3-4
# (the logits are about N(0, 0.9) over 50304 ids)
GREEDY_TOL = 0.5

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
GiB = 2 ** 30


class CompileClock:
    """Sums the backend-compile durations JAX reports. A persistent-cache
    hit is reported too, as the time it took to load."""

    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.seconds += duration
            self.count += 1


def check(ok: bool, what: str):
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")
    print(f"ok: {what}")


def require_tpu():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, but JAX's first device is "
            f"{dev.platform!r} ({dev.device_kind}); there is no CPU fallback")
    return dev


def use_compile_cache() -> str:
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    # cache every program, the small eager ones included
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax.config.jax_compilation_cache_dir


def init_params(cfg, plan):
    """Seeded random weights, built on the device under jit: an eager init
    would hold the per-layer trees and their stack at once."""
    init = jax.jit(lambda k: M.init_model(cfg, plan, k)[0])
    return jax.block_until_ready(init(jax.random.PRNGKey(SEED)))


def tree_bytes(tree) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(tree))


def describe(cfg):
    m = cfg.moe
    print(f"arch {cfg.name}: layers={cfg.num_layers} d_model={cfg.d_model} "
          f"heads={cfg.num_heads}/{cfg.num_kv_heads} "
          f"experts={m.num_experts} top-{m.experts_per_token} "
          f"d_expert={m.d_expert} vocab={cfg.vocab_size} dtype={cfg.dtype}")


# ---------------------------------------------------------------------------
# the one-chip reference
# ---------------------------------------------------------------------------

def _gap(logits, tokens):
    """logits [B, 1, V] f32, tokens [B] -> how far each token's logit lies
    below the greedy one (0 where it is the greedy token), [B]."""
    lg = logits[:, 0]
    return jnp.max(lg, -1) - jnp.take_along_axis(lg, tokens[:, None], -1)[:, 0]


def greedy_gap_fn(cfg, seq: int):
    """The reference decoder: one chip (NullDist), `M.prefill` then one
    `M.decode_step` per token, through their logits halves, at cache
    capacity `seq`. The returned gaps(params, prompts [B, L], tokens
    [B, N]) feeds it `tokens` (another program's greedy output) and gives
    each token's gap below the reference's greedy logit, [B, N]."""
    pplan, dplan, dist = null_plan("prefill"), null_plan("decode"), NullDist()
    prefill = jax.jit(lambda p, t: M.prefill_logits(
        p, {"tokens": t}, cfg, pplan, dist))
    step = jax.jit(lambda p, c, t, pos: M.decode_logits(
        p, c, t, pos, cfg, dplan, dist), donate_argnums=(1,))

    def gaps(params, prompts, tokens):
        prompts = jnp.asarray(prompts, jnp.int32)
        tokens = jnp.asarray(tokens, jnp.int32)
        L = prompts.shape[1]
        logits, caches = prefill(params, prompts)
        caches = kvcache.pad_to_capacity(cfg, caches, L, seq)
        out = [_gap(logits, tokens[:, 0])]
        for i in range(tokens.shape[1] - 1):
            logits, caches = step(params, caches, tokens[:, i:i + 1],
                                  jnp.int32(L + i))
            out.append(_gap(logits, tokens[:, i + 1]))
        return np.stack(jax.device_get(out), 1)

    return gaps


def check_greedy(gaps, what: str):
    print(f"{what}: {int((gaps == 0).sum())}/{gaps.size} tokens are the "
          f"reference's greedy token exactly; largest gap {gaps.max()}; "
          f"gaps above 0: {sorted(gaps[gaps > 0].tolist())}")
    check(bool(np.all(gaps < GREEDY_TOL)),
          f"{what}: every token is the reference's greedy choice "
          f"(within {GREEDY_TOL} of its best logit)")


# ---------------------------------------------------------------------------
# four chips: the sharded steps
# ---------------------------------------------------------------------------

def four_chips(cfg, clock: CompileClock):
    n_dev = len(jax.devices())
    check(n_dev == 4, f"JAX sees 4 devices (it sees {n_dev})")
    # Expert parallelism caps each expert's rows per source rank, one chip
    # caps them over the whole batch, so the two drop different tokens
    # (prefill: 64 local tokens, 12 rows per rank vs 48 in all). A
    # capacity of every local token drops none, and the two compute the
    # same thing.
    m = cfg.moe
    cfg = cfg.replace(num_layers=FOUR_CHIP_LAYERS, moe=dataclasses.replace(
        m, capacity_factor=m.num_experts / m.experts_per_token))
    describe(cfg)
    print(f"capacity_factor {cfg.moe.capacity_factor} (no token dropped)")
    axes, shape = FOUR_CHIP_MESH
    mesh = make_mesh(shape, axes)
    B, L, N = FOUR_CHIP_BATCH, FOUR_CHIP_PROMPT, FOUR_CHIP_NEW
    S = FOUR_CHIP_SEQ
    pre_cell = ShapeCell("prefill", L, B, "prefill")
    dec_cell = ShapeCell("decode", S, B, "decode")
    pre_plan = make_plan(cfg, pre_cell, axes, shape)
    dec_plan = make_plan(cfg, dec_cell, axes, shape)
    print(f"mesh {dict(zip(axes, shape))}: prefill ep_axis="
          f"{pre_plan.ep_axis}, decode ep_axis={dec_plan.ep_axis}, "
          f"attn {dec_plan.attn_mode}")
    prefill, _, pshard = steps.build_prefill(cfg, pre_cell, pre_plan, mesh)
    decode, _, dshard = steps.build_decode_step(cfg, dec_cell, dec_plan,
                                                mesh)
    check(jax.tree.leaves(pshard[0]) == jax.tree.leaves(dshard[0]),
          "prefill and decode lay the weights out the same way "
          "(one weight tree serves both)")

    c0 = clock.seconds
    params0 = init_params(cfg, null_plan("decode"))     # one chip
    params = jax.block_until_ready(jax.device_put(params0, pshard[0]))
    per_chip = collections.Counter()
    for leaf in jax.tree.leaves(params):
        for s in leaf.addressable_shards:
            per_chip[s.device.id] += s.data.nbytes
    total = tree_bytes(params0)
    print(f"weights: {total / GiB} GiB in all; per chip "
          + ", ".join(f"dev{d}={b / GiB} GiB" for d, b in
                      sorted(per_chip.items())))
    ffn = params["stack"]["periods"][0]["ffn"]
    n_exp = ffn["w_gate"].shape[1]
    check(all(s.data.shape[1] == n_exp // 4
              for k in ("w_gate", "w_up", "w_down")
              for s in ffn[k].addressable_shards),
          f"each chip holds {n_exp // 4} of the {n_exp} experts "
          f"(one copy of the weights across the mesh)")

    rng = np.random.default_rng(SEED)
    prompts = rng.integers(1, cfg.vocab_size, (B, L)).astype(np.int32)
    tokens_sh = jax.device_put(prompts, pshard[1]["tokens"])
    pre_text = prefill.lower(params, {"tokens": tokens_sh}).compile() \
        .as_text()
    print(f"prefill program: all-to-all={'all-to-all' in pre_text} "
          f"tpu_custom_call={'tpu_custom_call' in pre_text}")
    tok0, caches = prefill(params, {"tokens": tokens_sh})
    caches = kvcache.pad_to_capacity(cfg, caches, L, S)
    caches = jax.device_put(caches, dshard[1])
    tok = jax.device_put(tok0, dshard[2])
    dec_text = decode.lower(params, caches, tok, jnp.int32(L)).compile() \
        .as_text()
    check("all-to-all" in dec_text,
          "compiled decode program holds the expert all-to-all")
    print(f"decode program: tpu_custom_call="
          f"{'tpu_custom_call' in dec_text}")

    got, step_s = [tok], []
    for i in range(N - 1):
        t0 = time.perf_counter()
        tok, caches = decode(params, caches, tok, jnp.int32(L + i))
        tok.block_until_ready()
        step_s.append(time.perf_counter() - t0)
        got.append(tok)
    got = np.concatenate(jax.device_get(got), 1)
    print(f"sharded decode step: median {statistics.median(step_s[1:])} s "
          f"(first {step_s[0]} s); compile {clock.seconds - c0} s in all")
    stats = jax.devices()[0].memory_stats() or {}
    print(f"dev0 peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
    print(f"sharded tokens (four chips): {got.tolist()}")
    check_greedy(greedy_gap_fn(cfg, S)(params0, prompts, got),
                 "four chips vs the one-chip model")


def main():
    dev = require_tpu()
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"jax {jax.__version__}; compile cache {use_compile_cache()}")
    clock = CompileClock()
    t0 = time.perf_counter()
    four_chips(get_arch(ARCH), clock)
    print(f"total {time.perf_counter() - t0} s; backend compile "
          f"{clock.seconds} s over {clock.count} programs")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
