"""KV-cache management for the serving engine.

Caches come from ``models.model.init_cache`` as a pytree
``{"periods": tuple(stacked-per-period), "rem": tuple}``. This module owns
the structural knowledge of where the *sequence* dimension lives in each
leaf and which leaves are *positional* (indexed by absolute position, one
entry per position up to the cache's capacity) versus *recurrent* (state
of a fixed size, whatever the position). The split matters because a
prefill at prompt length L yields positional leaves L long, which
``pad_to_capacity`` grows to the engine's capacity, while recurrent
leaves already have their final shape and are left as they are.

Leaf classes (leaf key -> class):
  k, v (full attention)   positional  (seq dim: 2 after the batch dim)
  k, v (sliding window)   recurrent   (ring buffer of the window's size)
  c_kv, k_rope (MLA)      positional  (seq dim: 1)
  conv, ssm (mamba)       recurrent
  wkv, shift (rwkv)       recurrent
  cross k, v              positional  (read-only after prefill)
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig

# leaf-name -> (class, seq_dim_after_batch) for non-window attention
_POSITIONAL_SEQ_DIM = {"k": 2, "v": 2, "c_kv": 1, "k_rope": 1}
_RECURRENT_KEYS = {"conv", "ssm", "wkv", "shift"}


def _layer_spec_for_path(cfg: ModelConfig, path) -> LayerSpec:
    """Map a cache-tree path to the LayerSpec that produced it.

    Paths look like ("periods", i, <stack keys...>) or ("rem", i, ...);
    index i is the position within cfg.period.
    """
    idx = path[1].idx if hasattr(path[1], "idx") else path[1]
    return cfg.period[idx % len(cfg.period)]


def _leaf_info(cfg: ModelConfig, path) -> Tuple[str, int]:
    """(class, seq_dim) for one cache leaf. class: 'positional'|'recurrent'.
    seq_dim is the GLOBAL-array dim holding absolute positions (-1: none).
    Dims are counted on the unstacked [B, ...] layer cache; the 'periods'
    branch carries one extra leading stack dim handled by callers."""
    spec = _layer_spec_for_path(cfg, path)
    names = [p.key for p in path if hasattr(p, "key")]
    leaf = names[-1]
    group = names[-2]                      # mixer | ffn | cross
    if leaf in _RECURRENT_KEYS:
        return "recurrent", -1
    if group == "cross":
        return "positional", 2             # enc cache: fixed capacity
    if spec.mixer == "attn_local" and cfg.sliding_window and leaf in ("k", "v"):
        return "recurrent", -1             # ring buffer
    return "positional", _POSITIONAL_SEQ_DIM[leaf]


def classify(cfg: ModelConfig, caches) -> Any:
    """Pytree (same structure as caches) of 'positional'|'recurrent'."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: _leaf_info(cfg, path)[0], caches)


def pad_to_capacity(cfg: ModelConfig, caches, from_seq: int, to_seq: int):
    """Grow every positional leaf's sequence dim from_seq -> to_seq with
    zeros (prefill produced capacity from_seq; the engine runs at to_seq)."""
    assert to_seq >= from_seq

    def pad(path, x):
        cls, dim = _leaf_info(cfg, path)
        stacked = (path[0].key if hasattr(path[0], "key") else path[0]) == "periods"
        if cls == "recurrent" or dim < 0:
            return x
        d = dim + (1 if stacked else 0)
        if x.shape[d] != from_seq:          # e.g. cross cache (enc capacity)
            return x
        widths = [(0, 0)] * x.ndim
        widths[d] = (0, to_seq - from_seq)
        return jnp.pad(x, widths)

    return jax.tree_util.tree_map_with_path(pad, caches)


def insert_slot(caches, sub, slot: int, *, stacked_batch_dim: Dict = None):
    """Scatter a single-request cache `sub` (batch dim size 1) into batch
    index `slot` of the engine's caches. Batch dim: 0 for 'rem' leaves,
    1 for 'periods' leaves (stacked over periods)."""
    def ins(path, full, one):
        stacked = (path[0].key if hasattr(path[0], "key") else path[0]) == "periods"
        b_dim = 1 if stacked else 0
        idx = [slice(None)] * full.ndim
        idx[b_dim] = slot
        one_squeezed = jnp.squeeze(one, axis=b_dim)
        return full.at[tuple(idx)].set(one_squeezed)

    return jax.tree_util.tree_map_with_path(
        lambda path, f, o: ins(path, f, o), caches, sub)


def memory_bytes(caches) -> int:
    return int(sum(x.size * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(caches)))

