"""Distributed-numerics tests: the shard_map production path must agree
with the single-device (NullDist) path bit-for-bit in structure and within
bf16 tolerance in values.

Each test runs in a SUBPROCESS with XLA_FLAGS forcing 8 host devices —
jax locks the device count on first init, and the main pytest process must
keep seeing 1 device (smoke tests depend on it)."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, n_dev: int = 8) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={n_dev}").strip()
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-4000:]}"
    return json.loads(out.stdout.strip().splitlines()[-1])


COMMON = r"""
import json
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_arch, reduced_config
from repro.launch import steps as S
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.sharding.dist import NullDist
from repro.sharding.plans import make_plan, null_plan
from repro.configs.base import ShapeCell
from jax.sharding import NamedSharding, PartitionSpec as P

def cfg_for(arch, **kw):
    cfg = reduced_config(get_arch(arch))
    return cfg.replace(**kw) if kw else cfg

def put(tree, specs, mesh):
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs,
        is_leaf=lambda s: isinstance(s, P))
"""


@pytest.mark.parametrize("arch", ["olmoe-1b-7b", "starcoder2-3b",
                                  "jamba-v0.1-52b"])
def test_train_step_matches_single_device(arch):
    res = run_sub(COMMON + f"""
arch = {arch!r}
cfg = cfg_for(arch, num_heads=4, num_kv_heads=2)
B, Sq = 4, 32
shape = ShapeCell("t", Sq, B, "train")
mesh = make_mesh((2, 4), ("data", "model"))

tok = jax.random.randint(jax.random.PRNGKey(1), (B, Sq), 0, cfg.vocab_size)

# single-device reference loss (same init key)
plan0 = null_plan("train")
params0, _ = M.init_model(cfg, plan0, jax.random.PRNGKey(0))
loss0 = M.train_loss(params0, {{"tokens": tok}}, cfg, plan0, NullDist(),
                     remat=False)

# sharded: same params, global batch sharded
plan = make_plan(cfg, shape, ("data", "model"), (2, 4), fsdp=False)
pspecs = S.abstract_model(cfg, plan)[1]
import functools
from repro.sharding.dist import Dist
dist = Dist(dict(data=2, model=4))
def loss_fn(p, batch):
    return M.train_loss(p, batch, cfg, plan, dist, remat=False)
bspecs = {{"tokens": P(("data",), "model")}}
f = jax.jit(jax.shard_map(loss_fn, mesh=mesh,
            in_specs=(pspecs, bspecs), out_specs=P(), check_vma=False))
with mesh:
    params_sh = put(params0, pspecs, mesh)
    tok_sh = jax.device_put(tok, NamedSharding(mesh, P("data", "model")))
    loss1 = f(params_sh, {{"tokens": tok_sh}})
print(json.dumps({{"loss0": float(loss0), "loss1": float(loss1)}}))
""")
    assert res["loss0"] == pytest.approx(res["loss1"], rel=2e-2), res


@pytest.mark.parametrize("arch", ["olmoe-1b-7b", "gemma3-1b"])
def test_decode_step_matches_single_device(arch):
    """Sharded decode logits match single-device within bf16 reduction
    noise; greedy tokens agree except where the reference top-2 margin is
    itself inside that noise (argmax ties are order-sensitive)."""
    res = run_sub(COMMON + f"""
arch = {arch!r}
cfg = cfg_for(arch, num_heads=4, num_kv_heads=2)
B, cap = 8, 32
mesh = make_mesh((2, 4), ("data", "model"))
shape = ShapeCell("d", cap, B, "decode")

def logits_of(params, caches, tokens, pos, plan, dist):
    return M.decode_logits(params, caches, tokens, pos, cfg, plan, dist)[0]

plan0 = null_plan("decode")
params0, _ = M.init_model(cfg, plan0, jax.random.PRNGKey(0))
caches0, _ = M.init_cache(cfg, plan0, B, cap)
tok = jax.random.randint(jax.random.PRNGKey(2), (B, 1), 0, cfg.vocab_size)
l0 = logits_of(params0, caches0, tok, jnp.int32(0), plan0, NullDist())

plan = make_plan(cfg, shape, ("data", "model"), (2, 4), fsdp=False)
pspecs = S.abstract_model(cfg, plan)[1]
_, cspecs = S.abstract_cache(cfg, plan, B, cap)
from repro.sharding.dist import Dist
dist = Dist(dict(data=2, model=4))
def step(p, c, t, pos):
    lg = logits_of(p, c, t, pos, plan, dist)
    return dist.all_gather(lg, plan.vocab_axis, dim=-1)
tok_spec = P(plan.batch_axes, None)
f = jax.jit(jax.shard_map(step, mesh=mesh,
            in_specs=(pspecs, cspecs, tok_spec, P()),
            out_specs=P(plan.batch_axes, None, None), check_vma=False))
with mesh:
    params_sh = put(params0, pspecs, mesh)
    caches_sh = put(caches0, cspecs, mesh)
    tok_sh = jax.device_put(tok, NamedSharding(mesh, P(plan.batch_axes, None)))
    l1 = f(params_sh, caches_sh, tok_sh, jnp.int32(0))
l0f = np.asarray(l0[:, 0], np.float32); l1f = np.asarray(l1[:, 0], np.float32)
max_diff = float(np.abs(l0f - l1f).max())
flips_ok = True
for b in range(B):
    a0, a1 = int(l0f[b].argmax()), int(l1f[b].argmax())
    if a0 != a1:
        top2 = np.sort(l0f[b])[-2:]
        flips_ok &= bool(top2[1] - top2[0] < 0.05)   # only near-ties may flip
print(json.dumps({{"max_diff": max_diff, "flips_ok": flips_ok}}))
""")
    assert res["max_diff"] < 0.05, res
    assert res["flips_ok"], res


@pytest.mark.parametrize("arch", ["olmoe-1b-7b", "starcoder2-3b"])
def test_prefill_matches_single_device(arch):
    """Sequence-sharded prefill (tokens split over `model`, which also
    shards the vocab) fills the same KV cache and gives the same last-
    position logits as the single-device prefill."""
    res = run_sub(COMMON + f"""
arch = {arch!r}
cfg = cfg_for(arch, num_heads=4, num_kv_heads=2)
B, L = 4, 16
mesh = make_mesh((2, 4), ("data", "model"))
shape = ShapeCell("p", L, B, "prefill")
tok = jax.random.randint(jax.random.PRNGKey(1), (B, L), 0, cfg.vocab_size)
params0, _ = M.init_model(cfg, null_plan("prefill"), jax.random.PRNGKey(0))
l0, c0 = M.prefill_logits(params0, {{"tokens": tok}}, cfg,
                          null_plan("prefill"), NullDist())

plan = make_plan(cfg, shape, ("data", "model"), (2, 4), fsdp=False)
pspecs = S.abstract_model(cfg, plan)[1]
_, cspecs = S.abstract_cache(cfg, plan, B, L)
from repro.sharding.dist import Dist
dist = Dist(dict(data=2, model=4))
def step(p, t):
    lg, c = M.prefill_logits(p, {{"tokens": t}}, cfg, plan, dist)
    return dist.all_gather(lg, plan.vocab_axis, dim=-1), c
tok_spec = P(plan.batch_axes, plan.seq_axis)
f = jax.jit(jax.shard_map(step, mesh=mesh, in_specs=(pspecs, tok_spec),
            out_specs=(P(plan.batch_axes, None, None), cspecs),
            check_vma=False))
with mesh:
    l1, c1 = f(put(params0, pspecs, mesh),
               jax.device_put(tok, NamedSharding(mesh, tok_spec)))
f32 = lambda x: np.asarray(x, np.float32)
cache_diff = max(float(np.abs(f32(a) - f32(b)).max())
                 for a, b in zip(jax.tree.leaves(c0), jax.tree.leaves(c1)))
l0f, l1f = f32(l0[:, 0]), f32(l1[:, 0])
print(json.dumps({{"logit_diff": float(np.abs(l0f - l1f).max()),
                   "logit_scale": float(np.abs(l0f[np.isfinite(l0f)]).max()),
                   "cache_diff": cache_diff}}))
""")
    assert res["cache_diff"] < 0.05, res
    assert res["logit_diff"] < 0.05, res


def test_elastic_checkpoint_across_meshes(tmp_path):
    """Save params trained on a (4,2) mesh layout; restore on (2,2) AND on
    a single device — all three produce the same train-step loss."""
    res = run_sub(COMMON + f"""
import os
from repro.training import checkpoint as ckpt
arch = "olmoe-1b-7b"
cfg = cfg_for(arch, num_heads=4, num_kv_heads=2)
B, Sq = 4, 16
shape = ShapeCell("t", Sq, B, "train")
tok = jax.random.randint(jax.random.PRNGKey(1), (B, Sq), 0, cfg.vocab_size)
ckdir = {str(tmp_path)!r}

# "train" on (4,2): init sharded, save
mesh42 = make_mesh((4, 2), ("data", "model"))
plan42 = make_plan(cfg, shape, ("data", "model"), (4, 2), fsdp=False)
pspecs42 = S.abstract_model(cfg, plan42)[1]
params, _ = M.init_model(cfg, null_plan("train"), jax.random.PRNGKey(0))
with mesh42:
    params_sh = put(params, pspecs42, mesh42)
ckpt.save(params_sh, ckdir, 1, n_shards=4)

# restore on (2,2) with that mesh's shardings
mesh22 = make_mesh((2, 2), ("data", "model"))
plan22 = make_plan(cfg, shape, ("data", "model"), (2, 2), fsdp=False)
pspecs22 = S.abstract_model(cfg, plan22)[1]
shard22 = jax.tree.map(lambda s: NamedSharding(mesh22, s), pspecs22,
                       is_leaf=lambda s: isinstance(s, P))
restored22, at = ckpt.restore(params_sh, ckdir, shardings=shard22)

# restore single-device
restored1, _ = ckpt.restore(params_sh, ckdir)

loss_ref = float(M.train_loss(params, {{"tokens": tok}}, cfg,
                              null_plan("train"), NullDist(), remat=False))
loss1 = float(M.train_loss(jax.tree.map(lambda x: jnp.asarray(np.asarray(x)), restored1),
              {{"tokens": tok}}, cfg, null_plan("train"), NullDist(),
              remat=False))
ok_tree = all(bool((np.asarray(a) == np.asarray(b)).all())
              for a, b in zip(jax.tree.leaves(params),
                              jax.tree.leaves(restored22)))
print(json.dumps({{"loss_ref": loss_ref, "loss1": loss1, "tree22": ok_tree,
                   "step": at}}))
""")
    assert res["tree22"] is True
    assert res["loss_ref"] == pytest.approx(res["loss1"], rel=1e-3)
    assert res["step"] == 1
