"""Seeded weights, made on the device in the type they are served in.

The benchmark makes the weights, not the program: one jitted call from
``--seed`` gives the whole parameter tree in the program's layout
(fused ``wqkv`` = [q | k | v] columns, fused swiglu ``w1`` = [gate | up]),
and the plain reference asks this module for the same leaves one layer
at a time, so it never needs the tree the program holds.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_TOP, _LAYER = 0, 1


def seed_key(seed: int):
    """A key from any whole number: the driver's seeds pass 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _dense(key, m, n, dtype):
    return (jax.random.normal(key, (m, n), jnp.float32)
            / jnp.sqrt(jnp.float32(m))).astype(dtype)


def _scale(key, d, dtype):
    return (1.0 + 0.1 * jax.random.normal(key, (d,), jnp.float32)).astype(dtype)


def make_layer(key, cfg: dict, index, dtype):
    """Leaves of decoder layer ``index`` (may be traced)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = d // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * hd
    k = jax.random.split(jax.random.fold_in(
        jax.random.fold_in(key, _LAYER), index), 6)
    return {"ln1": {"scale": _scale(k[0], d, dtype)},
            "wqkv": _dense(k[1], d, d + 2 * kv, dtype),
            "wo": _dense(k[2], d, d, dtype),
            "ln2": {"scale": _scale(k[3], d, dtype)},
            "w1": _dense(k[4], d, 2 * f, dtype),
            "w2": _dense(k[5], f, d, dtype)}


def make_top(key, cfg: dict, dtype):
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    k = jax.random.split(jax.random.fold_in(key, _TOP), 3)
    return {"embed": (0.02 * jax.random.normal(k[0], (v, d), jnp.float32)
                      ).astype(dtype),
            "ln_f": {"scale": _scale(k[1], d, dtype)},
            "unembed": _dense(k[2], d, v, dtype)}


def make_params(cfg: dict, seed: int, dtype, sharding=None):
    """The whole tree in one jitted call."""
    n_layers = cfg["num_hidden_layers"]

    def build(key):
        p = make_top(key, cfg, dtype)
        p["blocks"] = [make_layer(key, cfg, i, dtype)
                       for i in range(n_layers)]
        return p

    return jax.jit(build, out_shardings=sharding)(seed_key(seed))
