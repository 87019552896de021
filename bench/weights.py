"""Seeded random weights, the same for the program and for the reference.

Every tensor is drawn from a key made of the run's seed, the tensor's name
and its layer, in bfloat16 (the served type). An architecture's reference
(``bench/references``) states which tensors a layer has and draws them one
layer at a time, so it holds no more than a layer and never reads an array
the program made; its adapter (``bench/adapters``) draws the same tensors
on the program's device in one jitted call and lays them out as the
program expects.

Norm gains are drawn around 1 as the published models state them
(``y = x / rms(x) * g``).
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

BF16 = jnp.bfloat16
#: spread of the norm gains around 1 and of the projection biases
GAIN_STD = 0.1
BIAS_STD = 0.1


def seed_key(seed: int):
    """A key for any whole seed, also one wider than 32 bits."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def draw(key, shape, kind, fan_in=None):
    """``matrix``: N(0, 1/fan_in), fan_in the first dimension unless given;
    ``gain``: around 1; ``bias``: around 0."""
    n = jax.random.normal(key, shape, jnp.float32)
    if kind == "gain":
        return jnp.clip(1.0 + GAIN_STD * n, 0.5, 1.98).astype(BF16)
    if kind == "bias":
        return (BIAS_STD * n).astype(BF16)
    return (n / jnp.sqrt(jnp.float32(fan_in or shape[0]))).astype(BF16)


def tensor_key(seed_k, name: str, layer):
    return jax.random.fold_in(jax.random.fold_in(seed_k, zlib.crc32(name.encode())),
                              layer)
