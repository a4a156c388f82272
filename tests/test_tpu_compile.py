"""Compile-only checks of the Pallas kernels and the decode path for a
described TPU v5e.

The TPU compiler compiles for a chip that is described, not attached, so
these tests run on a CPU-only host: nothing executes, but the compiler
refuses what the chip would refuse (VMEM over the scoped limit, tiles not
aligned to the hardware). Each kernel test asserts that the compiled
program still holds the Mosaic kernel (`tpu_custom_call`), i.e. that
nothing fell back to plain XLA.

The topology is described inside a fixture and never while a module is
imported: only one process at a time may load the TPU library, and the
test workers all import this file.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.kernels.moe_gmm import moe_gmm_pallas
from repro.models.layers.moe import capacity

PROMPT_TOKENS = 64          # one 64-token prefill sets the expert capacity
DECODE_SLOTS = 8            # the engine's decode wave: one token a slot, and
                            # each expert holds one row a slot


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # any failure means no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _struct(shape, sharding, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _expert_weights(cfg, sharding):
    e, d, f = cfg.moe.num_experts, cfg.d_model, cfg.moe.d_expert
    return (_struct((e, d, f), sharding), _struct((e, d, f), sharding),
            _struct((e, f, d), sharding))


@pytest.mark.parametrize("arch", ["olmoe-1b-7b", "granite-moe-3b-a800m"])
def test_moe_gmm_compiles_at_prefill_width(arch, one_chip):
    cfg = get_arch(arch)
    m = cfg.moe
    t = capacity(PROMPT_TOKENS, m.experts_per_token, m.num_experts,
                 m.capacity_factor)
    x = _struct((m.num_experts, t, cfg.d_model), one_chip)
    compiled = jax.jit(moe_gmm_pallas).lower(
        x, *_expert_weights(cfg, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("arch", ["olmoe-1b-7b", "granite-moe-3b-a800m"])
def test_moe_gmm_compiles_in_decode_wave_form(arch, one_chip):
    """One row per slot in every expert: x [E, DECODE_SLOTS, D]."""
    cfg = get_arch(arch)
    m = cfg.moe
    x = _struct((m.num_experts, DECODE_SLOTS, cfg.d_model), one_chip)
    compiled = jax.jit(moe_gmm_pallas).lower(
        x, *_expert_weights(cfg, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("arch", ["olmoe-1b-7b", "granite-moe-3b-a800m"])
def test_decode_wave_calls_moe_gmm_once_over_all_slots(arch, one_chip,
                                                       monkeypatch):
    """The engine's decode wave (two layers, the Pallas kernel as on a
    TPU) steps every slot in one batched call: each layer's kernel takes
    x [E, DECODE_SLOTS, D], with no slot axis of its own."""
    from repro.kernels import ops
    from repro.models import model as M
    from repro.serving.engine import Engine
    from repro.sharding.plans import null_plan

    monkeypatch.setattr(ops, "moe_gmm", moe_gmm_pallas)
    cfg = dataclasses.replace(get_arch(arch), num_layers=2)
    like = jax.eval_shape(lambda k: M.init_model(cfg, null_plan("decode"),
                                                 k)[0], jax.random.PRNGKey(0))
    eng = Engine(cfg, like, max_batch=DECODE_SLOTS, max_seq=64, eos_id=-1)

    def on_chip(t):
        return jax.tree.map(lambda a: _struct(a.shape, one_chip, a.dtype), t)

    text = eng._decode_wave.lower(
        on_chip(like), on_chip(eng.caches),
        _struct((DECODE_SLOTS, 1), one_chip, jnp.int32),
        _struct((DECODE_SLOTS,), one_chip, jnp.int32)).compile().as_text()
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    x_e = f"bf16[{cfg.moe.num_experts},{DECODE_SLOTS},{cfg.d_model}]"
    assert kernels and all(x_e in line for line in kernels), kernels


def test_attention_decode_compiles_at_granite_width(one_chip):
    """The decode wave's attention at granite-moe-3b-a800m's width: 24
    heads over 8 KV heads of 64, a cache of 32 slots x 1024 positions,
    each slot at its own position. The cache is donated, and the compiled
    program writes the new K/V into it in place."""
    from repro.models.layers.attention import attention_decode, \
        init_attention
    from repro.sharding.dist import NullDist
    from repro.sharding.plans import null_plan

    cfg = get_arch("granite-moe-3b-a800m")
    slots, seq = 32, 1024
    plan = null_plan("decode")
    params = jax.eval_shape(lambda k: init_attention(cfg, plan, k)[0],
                            jax.random.PRNGKey(0))
    kv = (slots, cfg.num_kv_heads, seq, cfg.head_dim)
    assert kv == (32, 8, 1024, 64) and cfg.num_heads == 24

    def step(params, x, cache, pos):
        return attention_decode(params, x, cache, pos, cfg, plan, NullDist())

    compiled = jax.jit(step, donate_argnums=(2,)).lower(
        jax.tree.map(lambda a: _struct(a.shape, one_chip, a.dtype), params),
        _struct((slots, 1, cfg.d_model), one_chip),
        {"k": _struct(kv, one_chip), "v": _struct(kv, one_chip)},
        _struct((slots,), one_chip, jnp.int32)).compile()
    y, cache = compiled.out_info
    assert y.shape == (slots, 1, cfg.d_model)
    assert cache["k"].shape == kv and cache["v"].shape == kv
    assert "input_output_alias" in compiled.as_text()
