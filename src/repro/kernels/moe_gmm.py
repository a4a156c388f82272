"""Pallas TPU kernel: grouped expert SwiGLU matmul (the MoE FFN hot spot).

The paper's expert computation (dense per-expert FFN over the A2A'd token
buffers) is the dominant MoE compute. TPU adaptation:
instead of a CUTLASS grouped GEMM over ragged token groups, we use the
static-capacity layout [E, T, D] produced by the dispatch scatter, tiled so
each (expert, token-tile, f-tile) step keeps its working set in VMEM and
feeds the MXU with 128-aligned tiles:

  grid (E, T/bt, F/bf) — sequential minor axis f accumulates the down-proj
  into a VMEM f32 accumulator; both matmuls and the SwiGLU fuse in one pass
  over the expert's weights, so expert weights stream HBM->VMEM exactly once
  per token-tile.

VMEM per step, bf16: the pipeline double-buffers every input and output
tile, and the f32 accumulator is single:
  2 * (x bt*D + w_gate/w_up/w_down 3*D*bf + out bt*D) * 2 B + acc bt*D*4 B
At bt=128, bf=256 that is 9 MiB for D=2048 (olmoe), 18 MiB for D=4096 and
31.5 MiB for D=7168 (deepseek-v3). The TPU compiler's default scoped VMEM
limit on v5e is 16 MiB, so it refuses D=4096 and D=7168 at block_f=256
(RESOURCE_EXHAUSTED ... vmem); D=7168 is refused at block_f=128 too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, wg_ref, wu_ref, wd_ref, out_ref, acc_ref, *, n_f: int):
    f = pl.program_id(2)

    @pl.when(f == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0]                       # [bt, D]
    wg = wg_ref[0]                     # [D, bf]
    wu = wu_ref[0]
    wd = wd_ref[0]                     # [bf, D]
    g = jnp.dot(x, wg, preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu, preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    acc_ref[...] += jnp.dot(h, wd, preferred_element_type=jnp.float32)

    @pl.when(f == n_f - 1)
    def _flush():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_t", "block_f", "interpret"))
def moe_gmm_pallas(x, w_gate, w_up, w_down, *, block_t: int = 128,
                   block_f: int = 256, interpret: bool = False):
    """x: [E, T, D]; w_gate/w_up: [E, D, F]; w_down: [E, F, D] -> [E, T, D].

    T and F need not be tile multiples: the token and FFN axes zero-pad up
    to the block size (block_t itself shrinks to T when T is smaller), so
    arbitrary capacity factors run instead of tripping a divisibility
    assert. Zero token rows produce zero outputs (sliced off) and zero FFN
    columns contribute nothing to the down-projection, so padding is exact.
    """
    e, t, d = x.shape
    f = w_gate.shape[-1]
    # shrink tiles for small T/F, keeping them hardware-aligned (sublane x8
    # on the token axis, lane x128 on the FFN axis)
    bt = min(block_t, -(-t // 8) * 8)
    bf = min(block_f, -(-f // 128) * 128)
    t_pad = -(-t // bt) * bt
    f_pad = -(-f // bf) * bf
    if t_pad != t:
        x = jnp.pad(x, ((0, 0), (0, t_pad - t), (0, 0)))
    if f_pad != f:
        w_gate = jnp.pad(w_gate, ((0, 0), (0, 0), (0, f_pad - f)))
        w_up = jnp.pad(w_up, ((0, 0), (0, 0), (0, f_pad - f)))
        w_down = jnp.pad(w_down, ((0, 0), (0, f_pad - f), (0, 0)))
    n_t, n_f = t_pad // bt, f_pad // bf

    grid = (e, n_t, n_f)
    out = pl.pallas_call(
        functools.partial(_kernel, n_f=n_f),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bt, d), lambda e_, t_, f_: (e_, t_, 0)),
            pl.BlockSpec((1, d, bf), lambda e_, t_, f_: (e_, 0, f_)),
            pl.BlockSpec((1, d, bf), lambda e_, t_, f_: (e_, 0, f_)),
            pl.BlockSpec((1, bf, d), lambda e_, t_, f_: (e_, f_, 0)),
        ],
        out_specs=pl.BlockSpec((1, bt, d), lambda e_, t_, f_: (e_, t_, 0)),
        out_shape=jax.ShapeDtypeStruct((e, t_pad, d), x.dtype),
        # f32 accumulator persisted across the sequential f grid steps
        scratch_shapes=[pltpu.VMEM((bt, d), jnp.float32)],
        interpret=interpret,
    )(x, w_gate, w_up, w_down)
    return out[:, :t] if t_pad != t else out
