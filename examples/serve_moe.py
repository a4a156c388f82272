"""End-to-end serving driver (the paper's workload kind): batched requests
through the continuous-batching engine on a reduced MoE model.

  PYTHONPATH=src python examples/serve_moe.py [--arch olmoe-1b-7b]
      [--requests 12] [--max-batch 4]

Prints per-request completions, slot reuse, and tokens/s.
"""
import argparse
import time

import jax

from repro.configs import get_arch, reduced_config
from repro.models import model as M
from repro.serving.engine import Engine
from repro.sharding.plans import null_plan


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmoe-1b-7b")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=96)
    ap.add_argument("--new-tokens", type=int, default=12)
    args = ap.parse_args()

    cfg = reduced_config(get_arch(args.arch))
    params, _ = M.init_model(cfg, null_plan("decode"), jax.random.PRNGKey(0))
    print(f"arch={args.arch} (reduced) layers={cfg.num_layers} "
          f"d_model={cfg.d_model} vocab={cfg.vocab_size}")

    eng = Engine(cfg, params, max_batch=args.max_batch,
                 max_seq=args.max_seq, eos_id=-1)
    prompts = [[(7 * i + j) % (cfg.vocab_size - 1) + 1 for j in range(6)]
               for i in range(args.requests)]
    rids = [eng.submit(p, max_new_tokens=args.new_tokens) for p in prompts]
    print(f"submitted {len(rids)} requests into {args.max_batch} slots "
          f"(continuous batching)")

    t0 = time.time()
    out = eng.run()
    dt = time.time() - t0
    total_tokens = sum(len(v) for v in out.values())
    dev = jax.devices()[0]
    print(f"completed {len(out)} requests, {total_tokens} tokens "
          f"in {dt:.1f}s ({total_tokens / dt:.1f} tok/s on {dev.platform} "
          f"{dev.device_kind})")
    for rid in rids[:4]:
        print(f"  req {rid}: prompt={prompts[rid]} -> {out[rid]}")
    if len(rids) > 4:
        print(f"  ... ({len(rids) - 4} more)")


if __name__ == "__main__":
    main()
