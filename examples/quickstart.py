"""Quickstart: the three layers of this repo in ~60 seconds on a laptop.

  1. ANALYSIS  — the paper's methodology: which network topology is the
                 most cost-effective for serving a given MoE model?
  2. MODEL     — a reduced MoE transformer (same family as olmoe-1b-7b):
                 one train step, prefill, and a few decode steps on CPU.
  3. KERNEL    — the Pallas MoE expert kernel vs its jnp oracle
                 (interpret mode on CPU; compiled on TPU).

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch, reduced_config
from repro.core import H100, Scenario, SearchSpec, make_cluster, solve
from repro.core.tco import cluster_tco
from repro.models import model as M
from repro.sharding.dist import NullDist
from repro.sharding.plans import null_plan

print("=" * 64)
print("1) ANALYSIS — topology cost-effectiveness (DeepSeek-V3, 64 XPUs,")
print("   chatbot scenario: TPOT=40ms, context=512, DBO+SD)")
print("=" * 64)
cfg_paper = get_arch("deepseek-v3")
sc = Scenario(40.0, 512)
for topo in ("scale-up", "scale-out", "torus", "fullmesh"):
    cl = make_cluster(topo, 64, H100)
    sol = solve(cfg_paper, cl, sc, SearchSpec(opts="dbo+sd"))
    cost = cluster_tco(cl).per_xpu(64)
    thpt = sol.throughput / 64
    print(f"  {topo:10s} {thpt:8.0f} tok/s/XPU  cost {cost:7.1f}/mo"
          f"  -> {thpt / cost:6.2f} tok/s per cost unit")

print()
print("=" * 64)
print("2) MODEL — reduced olmoe (64 experts->8): train / prefill / decode")
print("=" * 64)
cfg = reduced_config(get_arch("olmoe-1b-7b"))
plan, dist = null_plan("train"), NullDist()
params, _ = M.init_model(cfg, plan, jax.random.PRNGKey(0))
n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
print(f"  params: {n_params / 1e6:.2f}M  layers={cfg.num_layers} "
      f"experts={cfg.moe.num_experts} top-{cfg.moe.experts_per_token}")

tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                            cfg.vocab_size)
loss = M.train_loss(params, {"tokens": tokens}, cfg, plan, dist, remat=False)
print(f"  train loss (random init): {float(loss):.3f} "
      f"(ln V = {np.log(cfg.vocab_size):.3f})")

dplan = null_plan("decode")
tok, caches = M.prefill(params, {"tokens": tokens}, cfg,
                        null_plan("prefill"), dist)
seq = [int(t) for t in tok[:, 0]]
pos = tokens.shape[1]
from repro.serving import kvcache
caches = kvcache.pad_to_capacity(cfg, caches, pos, 32)
for _ in range(5):
    tok, caches = M.decode_step(params, caches, tok, jnp.int32(pos), cfg,
                                dplan, dist)
    seq.append(int(tok[0, 0]))
    pos += 1
print(f"  greedy continuation (request 0): {seq}")

print()
print("=" * 64)
on_tpu = jax.default_backend() == "tpu"
print(f"3) KERNEL — Pallas moe_gmm ({'compiled' if on_tpu else 'interpret'})"
      " vs jnp oracle")
print("=" * 64)
from repro.kernels import ref
from repro.kernels.moe_gmm import moe_gmm_pallas
ks = jax.random.split(jax.random.PRNGKey(2), 4)
e, t, d, f = 2, 128, 64, 256
x = jax.random.normal(ks[0], (e, t, d), jnp.float32) * 0.3
wg = jax.random.normal(ks[1], (e, d, f), jnp.float32) * 0.1
wu = jax.random.normal(ks[2], (e, d, f), jnp.float32) * 0.1
wd = jax.random.normal(ks[3], (e, f, d), jnp.float32) * 0.1
got = moe_gmm_pallas(x, wg, wu, wd, interpret=not on_tpu)
want = ref.moe_gmm_ref(x, wg, wu, wd)
err = float(jnp.max(jnp.abs(got - want)))
print(f"  [E={e}, T={t}, D={d}, F={f}]  max |pallas - ref| = {err:.2e}")
print("\nquickstart OK")
