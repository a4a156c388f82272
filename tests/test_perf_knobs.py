"""Numerics of the plan's optimization knobs: each must preserve model
outputs within quantization/bf16 tolerance vs the paper-faithful baseline.
Subprocess-based (needs 8 forced host devices)."""
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
from repro.sharding.dist import Dist, NullDist
from repro.sharding.plans import make_plan, null_plan
from repro.configs.base import ShapeCell
from jax.sharding import NamedSharding, PartitionSpec as P

def put(tree, specs, mesh):
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs,
        is_leaf=lambda s: isinstance(s, P))

def sharded_loss(cfg, params0, tok, mesh_shape, plan):
    mesh = make_mesh(mesh_shape, ("data", "model"))
    pspecs = S.abstract_model(cfg, plan)[1]
    dist = Dist(dict(zip(("data", "model"), mesh_shape)))
    def f(p, batch):
        return M.train_loss(p, batch, cfg, plan, dist, remat=False)
    bspecs = {"tokens": P(("data",), "model")}
    g = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(pspecs, bspecs),
                out_specs=P(), check_vma=False))
    with mesh:
        params_sh = put(params0, pspecs, mesh)
        tok_sh = jax.device_put(tok, NamedSharding(mesh, P("data", "model")))
        return float(g(params_sh, {"tokens": tok_sh}))
"""


def test_ring_attention_matches_megatron():
    """ring_attn prefill/train loss == Megatron-SP loss (same math,
    different collective schedule)."""
    res = run_sub(COMMON + """
cfg = reduced_config(get_arch("deepseek-67b")).replace(num_heads=8,
                                                       num_kv_heads=2)
B, Sq = 4, 32
shape = ShapeCell("t", Sq, B, "train")
tok = jax.random.randint(jax.random.PRNGKey(1), (B, Sq), 0, cfg.vocab_size)
params0, _ = M.init_model(cfg, null_plan("train"), jax.random.PRNGKey(0))

plan_m = make_plan(cfg, shape, ("data", "model"), (2, 4), fsdp=False)
plan_r = make_plan(cfg, shape, ("data", "model"), (2, 4), fsdp=False,
                   ring_attn=True)
assert plan_m.attn_mode == "head_tp"
l_m = sharded_loss(cfg, params0, tok, (2, 4), plan_m)
l_r = sharded_loss(cfg, params0, tok, (2, 4), plan_r)
print(json.dumps({"megatron": l_m, "ring": l_r}))
""")
    assert res["ring"] == pytest.approx(res["megatron"], rel=2e-2), res


def test_ag_fp8_close_to_baseline():
    """fp8 wire-format FFN gather: loss within fp8-quantization tolerance."""
    res = run_sub(COMMON + """
cfg = reduced_config(get_arch("starcoder2-3b"))
B, Sq = 4, 32
shape = ShapeCell("t", Sq, B, "train")
tok = jax.random.randint(jax.random.PRNGKey(1), (B, Sq), 0, cfg.vocab_size)
params0, _ = M.init_model(cfg, null_plan("train"), jax.random.PRNGKey(0))
plan_b = make_plan(cfg, shape, ("data", "model"), (2, 4), fsdp=False)
plan_q = make_plan(cfg, shape, ("data", "model"), (2, 4), fsdp=False,
                   ag_fp8=True)
l_b = sharded_loss(cfg, params0, tok, (2, 4), plan_b)
l_q = sharded_loss(cfg, params0, tok, (2, 4), plan_q)
print(json.dumps({"base": l_b, "fp8": l_q}))
""")
    assert res["fp8"] == pytest.approx(res["base"], rel=5e-2), res


def test_ffn_2d_decode_matches_baseline():
    """ffn_2d decode: same greedy logits as the baseline plan (pure
    resharding, no numerics change beyond reduction order)."""
    res = run_sub(COMMON + """
cfg = reduced_config(get_arch("deepseek-67b")).replace(
    num_heads=4, num_kv_heads=2, d_ff=128)
B, cap = 8, 32
shape = ShapeCell("d", cap, B, "decode")
params0, _ = M.init_model(cfg, null_plan("decode"), jax.random.PRNGKey(0))
tok = jax.random.randint(jax.random.PRNGKey(2), (B, 1), 0, cfg.vocab_size)

outs = {}
for name, kw in (("base", {}), ("ffn2d", {"ffn_2d": True})):
    mesh = make_mesh((2, 4), ("data", "model"))
    plan = make_plan(cfg, shape, ("data", "model"), (2, 4), fsdp=False, **kw)
    if name == "ffn2d":
        assert plan.ffn_2d, "ffn_2d not activated (divisibility?)"
    pspecs = S.abstract_model(cfg, plan)[1]
    caches0, _ = M.init_cache(cfg, null_plan("decode"), B, cap)
    _, cspecs = S.abstract_cache(cfg, plan, B, cap)
    dist = Dist(dict(data=2, model=4))
    def step(p, c, t, pos):
        return M.decode_step(p, c, t, pos, cfg, plan, dist)[0]
    tok_spec = P(plan.batch_axes, None)
    f = jax.jit(jax.shard_map(step, mesh=mesh,
                in_specs=(pspecs, cspecs, tok_spec, P()),
                out_specs=tok_spec, check_vma=False))
    with mesh:
        params_sh = put(params0, pspecs, mesh)
        caches_sh = put(caches0, cspecs, mesh)
        tok_sh = jax.device_put(tok, NamedSharding(mesh, tok_spec))
        outs[name] = np.asarray(f(params_sh, caches_sh, tok_sh,
                                  jnp.int32(0))).tolist()
match = sum(int(a == b) for a, b in zip(outs["base"], outs["ffn2d"]))
print(json.dumps({"match": match, "n": len(outs["base"]), **outs}))
""")
    assert res["match"] >= res["n"] - 1, res       # bf16 argmax near-ties


def test_a2a_fp8_close_to_baseline():
    """fp8 dispatch A2A: MoE train loss within quantization tolerance."""
    res = run_sub(COMMON + """
cfg = reduced_config(get_arch("olmoe-1b-7b")).replace(num_heads=4,
                                                      num_kv_heads=2)
B, Sq = 4, 32
shape = ShapeCell("t", Sq, B, "train")
tok = jax.random.randint(jax.random.PRNGKey(1), (B, Sq), 0, cfg.vocab_size)
params0, _ = M.init_model(cfg, null_plan("train"), jax.random.PRNGKey(0))
plan_b = make_plan(cfg, shape, ("data", "model"), (2, 4), fsdp=False)
plan_q = make_plan(cfg, shape, ("data", "model"), (2, 4), fsdp=False,
                   a2a_fp8=True)
l_b = sharded_loss(cfg, params0, tok, (2, 4), plan_b)
l_q = sharded_loss(cfg, params0, tok, (2, 4), plan_q)
print(json.dumps({"base": l_b, "fp8": l_q}))
""")
    assert res["fp8"] == pytest.approx(res["base"], rel=5e-2), res
