"""Seeded synthetic tensors, made on the device.

Real Boats and Cavity data are not in the repository, so each tensor is a
Gaussian Tucker core times orthonormal factors at the configuration's
published ranks, plus Gaussian noise at a stated share of its Frobenius
norm (the configuration's ``assumed.noise``).  This is the data model of the
repository's chip smoke run, kept here so that no change to the program can
change the benchmark's inputs.

Each tensor is made by one jitted call.  The noise is scaled by
``||core||_F / sqrt(size)``: the factors are orthonormal, so the low-rank
part's norm is the core's, and a standard normal tensor's norm is
``sqrt(size)`` to within ``1/sqrt(size)`` relative, which spares a second
pass over the tensor.
"""

from __future__ import annotations

import math
import string
from functools import partial

import jax
import jax.numpy as jnp


def key_for(seed: int, *path: int) -> jax.Array:
    """A PRNG key for ``seed`` (any non-negative int, 64 bits kept) and a
    path of sub-indices.  ``jax.random.key`` alone keeps only 32 bits."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    for p in path:
        key = jax.random.fold_in(key, p)
    return key


@partial(jax.jit, static_argnums=(1, 2, 3))
def _lowrank(key, shape: tuple, ranks: tuple, noise: float) -> jax.Array:
    keys = jax.random.split(key, len(shape) + 2)
    core = jax.random.normal(keys[0], ranks, jnp.float32)
    us = [jnp.linalg.qr(jax.random.normal(k, (d, r), jnp.float32))[0]
          for k, d, r in zip(keys[1:], shape, ranks)]
    letters = string.ascii_lowercase
    core_ix = letters[:len(shape)]
    out_ix = letters[len(shape):2 * len(shape)]
    spec = core_ix + "," + ",".join(o + c for o, c in zip(out_ix, core_ix))
    x = jnp.einsum(f"{spec}->{out_ix}", core, *us,
                   precision=jax.lax.Precision.HIGHEST)
    scale = noise * jnp.sqrt(jnp.sum(core * core)) / math.sqrt(math.prod(shape))
    return x + scale * jax.random.normal(keys[-1], shape, jnp.float32)


def lowrank(key, shape, ranks, noise: float) -> jax.Array:
    """Low-rank tensor of ``shape`` at ``ranks`` plus ``noise``-relative
    Gaussian noise, float32, on the default device."""
    return jax.block_until_ready(
        _lowrank(key, tuple(int(s) for s in shape),
                 tuple(int(r) for r in ranks), float(noise)))
