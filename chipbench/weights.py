"""Weights made by the benchmark, on the device, from the seed.

Every retraining job of a cell starts from the same train state: the
parameters below in float32 (the program's master type, the type the
serve plane stores) and a zero AdamW state. Each comes out of one jitted
call, so set-up pays two launches, not one per leaf. The reference in
`reference.py` calls `init_params` again after the timed window instead
of keeping a copy on the device.

The tree follows the program's parameter layout for a dense decoder:
one stacked segment of `num_layers` blocks, tied or untied embeddings,
and LayerNorm parameters only where the norm has them.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def key_from_seed(seed: int):
    """A PRNG key for any whole seed up to 64 bits: the low 32 bits make
    the key and the high bits are folded in."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def padded_vocab(vocab: int) -> int:
    return -(-vocab // 128) * 128


def shapes(model: dict) -> dict:
    """The parameter tree's shapes, in the program's layout."""
    d, f = model["d_model"], model["d_ff"]
    H, K = model["num_heads"], model["num_kv_heads"]
    hd = model.get("head_dim") or d // H
    L, Vp = model["num_layers"], padded_vocab(model["vocab_size"])
    norm = ({} if model["norm"] == "nonparam_ln" else
            {"scale": (d,), "bias": (d,)} if model["norm"] == "layernorm"
            else {"scale": (d,)})
    embed = {"table": (Vp, d)}
    if not model["tie_embeddings"]:
        embed["unembed"] = (d, Vp)
    block = {"ln1": {k: (L,) + s for k, s in norm.items()},
             "attn": {"wq": (L, d, H, hd), "wk": (L, d, K, hd),
                      "wv": (L, d, K, hd), "wo": (L, H, hd, d)},
             "ln2": {k: (L,) + s for k, s in norm.items()},
             "mlp": {"w_gate": (L, d, f), "w_up": (L, d, f),
                     "w_down": (L, f, d)}}
    return {"embed": embed, "final_norm": dict(norm), "segments": [block]}


def _std(name: str, shape, num_layers: int) -> float:
    """Embeddings 0.02; projections 1/sqrt(fan-in), with the residual
    outputs (wo, w_down) scaled down by sqrt(2 * layers)."""
    if name in ("table", "unembed"):
        return 0.02
    fan_in = shape[1] * shape[2] if name == "wo" else shape[-2]
    std = 1.0 / math.sqrt(fan_in)
    if name in ("wo", "w_down"):
        std /= math.sqrt(2 * num_layers)
    return std


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def init_params(model: dict, key):
    """float32 parameters from `key` (traced: call inside a jit)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes(model), is_leaf=_is_shape)
    keys = jax.random.split(key, len(flat))
    leaves = []
    for (path, shp), k in zip(flat, keys):
        name = path[-1].key
        if name == "scale":
            leaves.append(jnp.ones(shp, jnp.float32))
        elif name == "bias":
            leaves.append(jnp.zeros(shp, jnp.float32))
        else:
            std = _std(name, shp, model["num_layers"])
            leaves.append(std * jax.random.truncated_normal(
                k, -3.0, 3.0, shp, jnp.float32))
    return jax.tree_util.tree_unflatten(treedef, leaves)


@jax.jit
def _zero_opt(params):
    return {"mu": jax.tree.map(jnp.zeros_like, params),
            "nu": jax.tree.map(jnp.zeros_like, params),
            "count": jnp.zeros((), jnp.int32)}


def hashable(model: dict):
    """The dict's scalar entries, as a static argument of a jit."""
    return tuple(sorted((k, v) for k, v in model.items()
                        if not isinstance(v, (dict, list))))


def make_state(model: dict, seed: int):
    """A job's starting train state, {"params", "opt"}, on the device:
    the parameters of `make_params` (the same compiled program, so the
    reference starts from identical values) and a zero AdamW state."""
    params = make_params(model, seed)
    return {"params": params, "opt": _zero_opt(params)}


@functools.partial(jax.jit, static_argnums=(0,))
def _params(model_items, key):
    return init_params(dict(model_items), key)


def make_params(model: dict, seed: int):
    """The starting parameters alone (what the reference begins from)."""
    return _params(hashable(model), key_from_seed(seed))
