"""Production serving launcher: sharded prefill + decode loop on a mesh.

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.serve \
      --arch olmoe-1b-7b --reduced --mesh 2x4 --batch 8 --new-tokens 16

Exercises the same shard_map step the dry-run compiles: batch sharded over
(pod,)data, TP/EP over model, KV sequence-sharded, perf knobs optional
(--ffn-2d / --a2a-fp8). Single-host continuous batching lives in
repro.serving.engine; this launcher is the fleet-shaped batched path.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import get_arch, reduced_config
from repro.configs.base import ShapeCell
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_mesh
from repro.sharding.plans import make_plan


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmoe-1b-7b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mesh", default="2x4")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--ffn-2d", action="store_true")
    ap.add_argument("--a2a-fp8", action="store_true")
    args = ap.parse_args()

    shape_t = tuple(int(x) for x in args.mesh.split("x"))
    axes = ("pod", "data", "model")[-len(shape_t):]
    mesh = make_mesh(shape_t, axes)
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    print(f"mesh {dict(zip(axes, shape_t))}; arch {args.arch}"
          f"{' (reduced)' if args.reduced else ''}")

    plan_kw = dict(ffn_2d=args.ffn_2d, a2a_fp8=args.a2a_fp8)
    pre_cell = ShapeCell("p", args.prompt_len, args.batch, "prefill")
    dec_cell = ShapeCell("d", args.max_seq, args.batch, "decode")
    pre_plan = make_plan(cfg, pre_cell, axes, shape_t, **{
        k: v for k, v in plan_kw.items() if k != "ffn_2d"})
    dec_plan = make_plan(cfg, dec_cell, axes, shape_t, **plan_kw)

    prefill, pstructs, pshard = steps_mod.build_prefill(cfg, pre_cell,
                                                        pre_plan, mesh)
    decode, dstructs, dshard = steps_mod.build_decode_step(cfg, dec_cell,
                                                           dec_plan, mesh)

    from repro.models import model as M
    init = jax.jit(lambda k: M.init_model(cfg, pre_plan, k)[0],
                   out_shardings=pshard[0])
    rng = np.random.default_rng(0)
    tokens = rng.integers(1, cfg.vocab_size, (args.batch, args.prompt_len),
                          dtype=np.int32)
    with mesh:
        params = init(jax.random.PRNGKey(0))
        tok_sh = jax.device_put(tokens, pshard[1]["tokens"])
        t0 = time.time()
        next_tok, caches = prefill(params, {"tokens": tok_sh})
        next_tok.block_until_ready()
        t_prefill = time.time() - t0

        # prefill cache capacity == prompt_len; decode runs against the
        # decode-cell capacity — re-home the cache (pad along seq dims)
        from repro.serving import kvcache
        caches = kvcache.pad_to_capacity(cfg, caches, args.prompt_len,
                                         args.max_seq)
        caches = jax.device_put(caches, dshard[1])
        next_tok = jax.device_put(next_tok, dshard[2])

        out = [np.asarray(next_tok)]
        t0 = time.time()
        for i in range(args.new_tokens - 1):
            pos = jnp.int32(args.prompt_len + i)
            next_tok, caches = decode(params, caches, next_tok, pos)
            out.append(np.asarray(next_tok))
        dt = time.time() - t0

    seqs = np.concatenate(out, axis=1)
    thpt = args.batch * (args.new_tokens - 1) / dt
    dev = jax.devices()[0]
    print(f"prefill {args.batch}x{args.prompt_len} in {t_prefill:.2f}s; "
          f"decode {args.new_tokens - 1} steps in {dt:.2f}s "
          f"({thpt:.1f} tok/s on {len(jax.devices())} x {dev.platform} "
          f"{dev.device_kind})")
    for b in range(min(args.batch, 3)):
        print(f"  seq {b}: {seqs[b].tolist()}")
    print(f"plan: ffn_2d={dec_plan.ffn_2d} a2a_fp8={dec_plan.a2a_fp8} "
          f"attn={dec_plan.attn_mode} ep={dec_plan.ep_axis}")


if __name__ == "__main__":
    main()
