"""Compile-only checks of the Pallas kernels for a described TPU v5e.

The TPU compiler compiles for a chip that is described, not attached, so
these tests run on a CPU-only host: nothing executes, but the compiler
refuses what the chip would refuse (VMEM over the scoped limit, tiles not
aligned to the hardware). Each test asserts that the compiled program
still holds the Mosaic kernel (`tpu_custom_call`), i.e. that nothing fell
back to plain XLA.

The topology is described inside a fixture and never while a module is
imported: only one process at a time may load the TPU library, and the
test workers all import this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.kernels.flash_decode import flash_decode_pallas
from repro.kernels.moe_gmm import moe_gmm_pallas
from repro.models.layers.moe import capacity

PROMPT_TOKENS = 64          # one 64-token prefill sets the expert capacity
DECODE_SLOTS = 4            # the engine's decode wave vmaps over its slots


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
    """Capacity 1 per expert, vmapped over the engine's slots."""
    cfg = get_arch(arch)
    m = cfg.moe
    t = capacity(1, m.experts_per_token, m.num_experts, m.capacity_factor)
    assert t == 1
    x = _struct((DECODE_SLOTS, m.num_experts, t, cfg.d_model), one_chip)
    wave = jax.vmap(moe_gmm_pallas, in_axes=(0, None, None, None))
    compiled = jax.jit(wave).lower(
        x, *_expert_weights(cfg, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_decode_compiles_at_olmoe_width(one_chip):
    cfg = get_arch("olmoe-1b-7b")
    b, h, kh, hd, s = (DECODE_SLOTS, cfg.num_heads, cfg.num_kv_heads,
                       cfg.head_dim, 512)
    compiled = jax.jit(flash_decode_pallas).lower(
        _struct((b, h, hd), one_chip),
        _struct((b, kh, s, hd), one_chip),
        _struct((b, kh, s, hd), one_chip),
        _struct((), one_chip, jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
