"""Dispatching wrapper around the grouped expert matmul kernel.

The model calls `moe_gmm`. It runs the Pallas TPU kernel on a TPU backend
and the pure-jnp oracle on any other (the CPU tests, the dry-run grid).
The choice rests on the platform alone: the kernel pads the token and FFN
axes to its tiles itself, so every shape the model produces runs on it.
Interpret mode is an explicit argument of the kernel, which only the
kernel tests pass.
"""
from __future__ import annotations

import jax

from repro.kernels import ref as kref


def moe_gmm(x, w_gate, w_up, w_down):
    if jax.default_backend() != "tpu":
        return kref.moe_gmm_ref(x, w_gate, w_up, w_down)
    from repro.kernels.moe_gmm import moe_gmm_pallas
    return moe_gmm_pallas(x, w_gate, w_up, w_down)
