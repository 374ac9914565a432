"""Weights made by the benchmark, on the device, from the seed.

Every retraining job of a cell starts from the same train state: the
parameters of the cell's architecture module (`chipbench/arch/`) in
float32 (the program's master type, the type the serve plane stores)
and a zero AdamW state. Each comes out of one jitted call, so set-up
pays two launches, not one per leaf. The reference in `reference.py`
calls `make_params` again after the timed window instead of keeping a
copy on the device.
"""
from __future__ import annotations

import functools

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


@jax.jit
def _zero_opt(params):
    return {"mu": jax.tree.map(jnp.zeros_like, params),
            "nu": jax.tree.map(jnp.zeros_like, params),
            "count": jnp.zeros((), jnp.int32)}


def hashable(tree):
    """A configuration object (dicts, lists and scalars, nested), frozen
    to a static argument of a jit: dicts become frozensets of their
    (key, value) pairs, lists tuples. `thaw` gives the object back."""
    if isinstance(tree, dict):
        return frozenset((k, hashable(v)) for k, v in tree.items())
    if isinstance(tree, list):
        return tuple(hashable(v) for v in tree)
    return tree


def thaw(frozen):
    """The object that `hashable` froze, its dicts in key order."""
    if isinstance(frozen, frozenset):
        return {k: thaw(v) for k, v in sorted(frozen)}
    if isinstance(frozen, tuple):
        return [thaw(v) for v in frozen]
    return frozen


def make_state(arch, model: dict, seed: int):
    """A job's starting train state, {"params", "opt"}, on the device:
    the parameters of `make_params` (the same compiled program, so the
    reference starts from identical values) and a zero AdamW state."""
    params = make_params(arch, model, seed)
    return {"params": params, "opt": _zero_opt(params)}


@functools.partial(jax.jit, static_argnums=(0, 1))
def _params(arch, model_items, key):
    return arch.init_params(thaw(model_items), key)


def make_params(arch, model: dict, seed: int):
    """The starting parameters alone (what the reference begins from)."""
    return _params(arch, hashable(model), key_from_seed(seed))
