"""Seeded random weights, made by the benchmark and not by the program.

One function makes a layer's weights in the published layout from the
seed and the layer's index (`layer_weights`), and one the weights outside
the layers (`top_weights`). The program's tree (`program_params`) stacks
them under one `jax.jit`, on the device, in the dtype they are served in.
The plain reference (`reference.py`) calls the same two functions again,
one layer at a time, and gets the same numbers bit for bit: it never reads
what was handed to the program.

Published layout, per layer (D hidden, H heads, KV key/value heads, hd
head size, E experts, F expert width):

  attn_norm [D] f32   wq [D, H*hd]   wk, wv [D, KV*hd]   wo [H*hd, D]
  mlp_norm  [D] f32   router [D, E] f32
  w_gate, w_up [E, D, F]   w_down [E, F, D]

and outside the layers: embed [V, D], head [D, V] (untied only),
final_norm [D] f32. A norm's weight is 1 plus a small random offset; the router's weights
are scaled up (ROUTER_SCALE).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NORM_OFFSET_STD = 0.1
# The router's weights have std ROUTER_SCALE / sqrt(D), so its logits have
# std about ROUTER_SCALE. At std 1 the k-th and (k+1)-th of E router
# logits lie about a tenth apart, and which experts a token goes to is
# settled by rounding: the bfloat16 program and the float32 reference then
# route differently for reasons of no interest, and a control computed in
# fp8 cannot be told from the program. At 4 the choice is decisive.
ROUTER_SCALE = 4.0
TOP_INDEX = 1 << 20          # fold-in index of the weights outside the layers


def dims(c: dict) -> dict:
    """The sizes the weights and the reference need, from a config file."""
    n_exp = c.get("num_experts", c.get("num_local_experts"))
    return dict(
        L=c["num_hidden_layers"], D=c["hidden_size"],
        H=c["num_attention_heads"], KV=c["num_key_value_heads"],
        hd=c["head_dim"], E=n_exp, k=c["num_experts_per_tok"],
        F=c["intermediate_size"], V=c["vocab_size"],
        tied=bool(c["tie_word_embeddings"]),
        dtype=jnp.dtype(c["torch_dtype"]))


def seed_key(seed: int):
    """A PRNG key from any whole number: both 32-bit halves are folded in,
    so seeds past 2**32 give their own weights."""
    s = int(seed) % (1 << 64)
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, np.uint32(s & 0xFFFFFFFF))
    return jax.random.fold_in(k, np.uint32(s >> 32))


def _normal(key, shape, std, dt):
    return jax.random.normal(key, shape, dt) * jnp.asarray(std, dt)


def _norm_weight(key, d):
    return 1.0 + NORM_OFFSET_STD * jax.random.normal(key, (d,), jnp.float32)


def layer_weights(c: dict, key, layer):
    """Layer `layer`'s weights (an int or a traced int32), published layout."""
    m = dims(c)
    D, H, KV, hd, E, F, dt = (m[x] for x in ("D", "H", "KV", "hd", "E", "F",
                                              "dtype"))
    ks = jax.random.split(jax.random.fold_in(key, layer), 10)
    return {
        "attn_norm": _norm_weight(ks[0], D),
        "wq": _normal(ks[1], (D, H * hd), D ** -0.5, dt),
        "wk": _normal(ks[2], (D, KV * hd), D ** -0.5, dt),
        "wv": _normal(ks[3], (D, KV * hd), D ** -0.5, dt),
        "wo": _normal(ks[4], (H * hd, D), (H * hd) ** -0.5, dt),
        "mlp_norm": _norm_weight(ks[5], D),
        "router": _normal(ks[6], (D, E), ROUTER_SCALE * D ** -0.5,
                          jnp.float32),
        "w_gate": _normal(ks[7], (E, D, F), D ** -0.5, dt),
        "w_up": _normal(ks[8], (E, D, F), D ** -0.5, dt),
        "w_down": _normal(ks[9], (E, F, D), F ** -0.5, dt),
    }


def top_weights(c: dict, key):
    """Embedding, output head (untied only) and final norm. Logits come out
    about N(0, 1) at any width: the head's columns have norm about 1."""
    m = dims(c)
    D, V, dt = m["D"], m["V"], m["dtype"]
    ks = jax.random.split(jax.random.fold_in(key, TOP_INDEX), 3)
    out = {"final_norm": _norm_weight(ks[2], D)}
    if m["tied"]:
        out["embed"] = _normal(ks[0], (V, D), D ** -0.5, dt)
    else:
        out["embed"] = _normal(ks[0], (V, D), 1.0, dt)
        out["head"] = _normal(ks[1], (D, V), D ** -0.5, dt)
    return out


def program_params(c: dict, seed: int, like):
    """The program's weight tree, built on the device in one jitted call.

    `like` is the tree of the program's own init (`jax.eval_shape` of
    `repro.models.model.init_model`): its structure, shapes and dtypes are
    what the built tree must match, and its embedding rows give the padded
    vocabulary. Layout, as the program keeps it: stack.periods[i] holds the
    layers i, i+P, i+2P, ... stacked on a leading axis; a norm is kept as
    its weight minus 1; w_k and w_v as [D, KV, hd]; padded vocabulary rows
    and columns are zero."""
    m = dims(c)
    periods = like["stack"]["periods"]
    P, n_rem = len(periods), len(like["stack"]["rem"])
    n_per = (m["L"] - n_rem) // P if P else 0
    v_pad = like["embed"]["table"].shape[0]

    def layer_tree(w):
        lead = w["wk"].shape[:-2]
        kv = lead + (m["D"], m["KV"], m["hd"])
        return {
            "norm1": {"scale": w["attn_norm"] - 1.0},
            "mixer": {"w_q": w["wq"], "w_k": w["wk"].reshape(kv),
                      "w_v": w["wv"].reshape(kv), "w_o": w["wo"]},
            "norm2": {"scale": w["mlp_norm"] - 1.0},
            "ffn": {"router": w["router"], "w_gate": w["w_gate"],
                    "w_up": w["w_up"], "w_down": w["w_down"]},
        }

    def build(key):
        stacked = tuple(
            layer_tree(jax.vmap(lambda j, i=i: layer_weights(c, key, j * P + i))(
                jnp.arange(n_per, dtype=jnp.int32)))
            for i in range(P))
        rem = tuple(layer_tree(layer_weights(c, key, n_per * P + i))
                    for i in range(n_rem))
        top = top_weights(c, key)
        pad = v_pad - m["V"]
        embed = {"table": jnp.pad(top["embed"], ((0, pad), (0, 0)))}
        if not m["tied"]:
            embed["head"] = jnp.pad(top["head"], ((0, 0), (0, pad)))
        return {"embed": embed,
                "stack": {"periods": stacked, "rem": rem},
                "final_norm": {"scale": top["final_norm"] - 1.0}}

    params = jax.jit(build)(seed_key(seed))
    check_like(params, like)
    return params


def check_like(tree, like):
    """Raise unless `tree` has the structure, shapes and dtypes of `like`."""
    if jax.tree.structure(tree) != jax.tree.structure(like):
        raise ValueError(f"weight tree {jax.tree.structure(tree)} is not the "
                         f"program's {jax.tree.structure(like)}")
    for path, a, b in zip(jax.tree_util.tree_flatten_with_path(tree)[0],
                          jax.tree.leaves(tree), jax.tree.leaves(like)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"{jax.tree_util.keystr(path[0])}: built "
                             f"{a.shape} {a.dtype}, program {b.shape} {b.dtype}")
