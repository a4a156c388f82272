#!/usr/bin/env python3
"""Bring-up check: the MoE serving path on a TPU, at full model width.

  python3 chip_smoke.py                # one chip, through the Engine
  python3 chip_smoke.py --four-chips   # four chips, the sharded steps

One chip: olmoe-1b-7b at its published width (16 layers, d_model 2048,
64 experts top-8, d_expert 1024, vocab 50304) with random weights made
from a seed and initialised under `jax.jit`. `repro.serving.engine.Engine`
(4 slots, 512 positions) serves 8 requests of 32/64/128 prompt tokens and
32 new tokens each. The run fails unless every request finishes, each
request decoded alone with `M.prefill` and `M.decode_step` (fed the
engine's tokens) agrees that every engine token is its greedy choice,
and `ops.moe_gmm` on the real layer-0 expert weights matches
`kref.moe_gmm_ref` to bf16 tolerance.

Four chips: the `launch.steps` prefill and decode on a (data=1, model=4)
mesh, four experts-sharded layers at full width, one weight tree serving
both phases. The decode program must hold the expert all-to-all, and
the one-chip NullDist model must agree that every sharded token, fed the
reference's tokens, is its greedy choice.

"Agree" allows a near tie. Two programs that batch or shard the same
step round differently: on a v5e the 4-slot decode wave and a 1-slot one
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

import argparse
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
from repro.kernels import ops as kops  # noqa: E402
from repro.kernels import ref as kref  # noqa: E402
from repro.launch import steps  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models.layers.moe import capacity  # noqa: E402
from repro.serving import kvcache  # noqa: E402
from repro.serving.engine import Engine  # noqa: E402
from repro.sharding.dist import NullDist  # noqa: E402
from repro.sharding.plans import make_plan, null_plan  # noqa: E402

ARCH = "olmoe-1b-7b"
SEED = 0
# one chip: the engine and its requests
MAX_BATCH, MAX_SEQ = 4, 512
PROMPT_LENS = (32, 64, 128)       # each distinct length compiles a prefill
N_REQUESTS, NEW_TOKENS = 8, 32
WAVE_REPEATS = 10
# four chips: depth cut so the one-chip reference fits beside its shard
FOUR_CHIP_LAYERS = 4
FOUR_CHIP_MESH = (("data", "model"), (1, 4))
FOUR_CHIP_BATCH, FOUR_CHIP_PROMPT, FOUR_CHIP_NEW = 4, 64, 16
# how far below the reference's greedy logit a token may lie and still
# count as the same greedy choice: above the 0.148 rounding gap measured
# between the wave and a 1-slot program, far below a wrong token's 3-4
# (the logits are about N(0, 0.9) over 50304 ids)
GREEDY_TOL = 0.5
# bf16 tolerance of the kernel against the jnp oracle (as tests/test_kernels)
KERNEL_ATOL = KERNEL_RTOL = 5e-2

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
# one chip: the engine
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


def check_kernel(cfg, params):
    """ops.moe_gmm on the real layer-0 expert weights against the oracle,
    at the capacity a 64-token prefill gives each expert."""
    m = cfg.moe
    ffn = params["stack"]["periods"][0]["ffn"]
    w = tuple(ffn[k][0] for k in ("w_gate", "w_up", "w_down"))
    t = capacity(64, m.experts_per_token, m.num_experts, m.capacity_factor)
    x = jax.random.normal(jax.random.PRNGKey(SEED + 1),
                          (w[0].shape[0], t, cfg.d_model),
                          jnp.float32).astype(w[0].dtype)
    gmm = jax.jit(kops.moe_gmm)
    text = gmm.lower(x, *w).compile().as_text()
    check("tpu_custom_call" in text,
          "ops.moe_gmm runs the Pallas kernel (tpu_custom_call)")
    got = np.asarray(gmm(x, *w), np.float32)
    want = np.asarray(jax.jit(kref.moe_gmm_ref)(x, *w), np.float32)
    # a float32 reference on the first 8 experts only: all 64 in float32
    # would not fit beside the weights
    with jax.default_matmul_precision("highest"):
        truth = np.asarray(jax.jit(kref.moe_gmm_ref)(
            *(a[:8].astype(jnp.float32) for a in (x, *w))))
    err = np.abs(got - want)
    print(f"moe_gmm [E={x.shape[0]}, T={t}, D={cfg.d_model}, "
          f"F={m.d_expert}] layer 0: max|kernel-ref|={err.max()}; "
          f"experts 0-7: max|kernel-f32|={np.abs(got[:8] - truth).max()} "
          f"max|ref-f32|={np.abs(want[:8] - truth).max()} "
          f"max|f32|={np.abs(truth).max()}")
    check(bool(np.all(err <= KERNEL_ATOL + KERNEL_RTOL * np.abs(want))),
          f"kernel matches kref.moe_gmm_ref (atol=rtol={KERNEL_ATOL})")


def one_chip(cfg, clock: CompileClock, dev):
    describe(cfg)
    c0, t0 = clock.seconds, time.perf_counter()
    params = init_params(cfg, null_plan("decode"))
    print(f"init: {tree_bytes(params) / GiB} GiB of weights in "
          f"{time.perf_counter() - t0} s (compile {clock.seconds - c0} s)")

    eng = Engine(cfg, params, max_batch=MAX_BATCH, max_seq=MAX_SEQ,
                 eos_id=-1)
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(1, cfg.vocab_size,
                            PROMPT_LENS[i % len(PROMPT_LENS)]).tolist()
               for i in range(N_REQUESTS)]
    rids = [eng.submit(p, max_new_tokens=NEW_TOKENS) for p in prompts]
    c0, n0, t0 = clock.seconds, clock.count, time.perf_counter()
    out = eng.run()
    serve_s = time.perf_counter() - t0
    print(f"engine: {len(out)} requests in {serve_s} s, compile "
          f"{clock.seconds - c0} s over {clock.count - n0} programs")
    check(sorted(out) == sorted(rids)
          and all(len(out[r]) == NEW_TOKENS + 1 for r in rids),
          f"all {N_REQUESTS} requests finished with {NEW_TOKENS} new "
          f"tokens each")

    waves = []
    for _ in range(WAVE_REPEATS):
        t0 = time.perf_counter()
        toks, eng.caches = eng._decode_wave(eng.params, eng.caches,
                                            eng.last_tok, eng.pos)
        toks.block_until_ready()
        waves.append(time.perf_counter() - t0)
    print(f"decode wave ({MAX_BATCH} slots): median "
          f"{statistics.median(waves)} s, min {min(waves)} s "
          f"over {WAVE_REPEATS} waves")
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')} "
          f"({stats.get('peak_bytes_in_use', 0) / GiB} GiB) of "
          f"bytes_limit {stats.get('bytes_limit')}")

    wave_text = eng._decode_wave.lower(eng.params, eng.caches, eng.last_tok,
                                       eng.pos).compile().as_text()
    check("tpu_custom_call" in wave_text,
          "compiled decode wave holds the Pallas kernel (tpu_custom_call)")
    for L in PROMPT_LENS:
        pre_text = eng._prefill_fn(L).lower(
            eng.params, jnp.zeros((1, L), jnp.int32)).compile().as_text()
        check("tpu_custom_call" in pre_text,
              f"compiled prefill (L={L}) holds the Pallas kernel "
              f"(tpu_custom_call)")
    del eng, toks

    t0 = time.perf_counter()
    gaps_of = greedy_gap_fn(cfg, MAX_SEQ)
    gaps = np.concatenate([gaps_of(params, [prompt], [out[rid]])
                           for rid, prompt in zip(rids, prompts)])
    print(f"solo decoding of {N_REQUESTS} requests: "
          f"{time.perf_counter() - t0} s")
    check_greedy(gaps, "engine vs each request decoded alone")
    check_kernel(cfg, params)


# ---------------------------------------------------------------------------
# four chips: the sharded steps
# ---------------------------------------------------------------------------

def four_chips(cfg, clock: CompileClock):
    n_dev = len(jax.devices())
    check(n_dev == 4, f"--four-chips sees 4 devices (JAX sees {n_dev})")
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
    S = MAX_SEQ
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


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sharded path and its "
                         "one-chip comparison")
    args = ap.parse_args(argv)
    dev = require_tpu()
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"jax {jax.__version__}; compile cache {use_compile_cache()}")
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(get_arch(ARCH), clock)
    else:
        one_chip(get_arch(ARCH), clock, dev)
    print(f"total {time.perf_counter() - t0} s; backend compile "
          f"{clock.seconds} s over {clock.count} programs")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
