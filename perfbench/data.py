"""Inputs made from ``--seed``: the same seed always gives the same inputs.

Working sets and the chase's successor array are made on the device in one
jitted call each; nothing is built on the host.  These generators are the
benchmark's own, so that no change to the program moves them.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def key(seed: int):
    """A PRNG key for any non-negative seed, including ones past 32 bits."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0: {seed}")
    k = jax.random.key(seed % 2**32)
    return jax.random.fold_in(k, seed // 2**32) if seed >= 2**32 else k


@partial(jax.jit, static_argnames=("shape", "dtype"))
def _uniform(k, shape, dtype):
    return jax.random.uniform(k, shape, jnp.float32, 1.0, 2.0).astype(dtype)


def working_set(seed: int, shape, dtype):
    """Values in [1, 2): normal numbers with full mantissas, so no denormal
    slows the pipeline and any lost element or rounding step shows."""
    return _uniform(key(seed), tuple(shape), jnp.dtype(dtype).name)


@partial(jax.jit, static_argnames=("shape",))
def _successor(k, shape):
    n = shape[0] * shape[1]
    rest = jax.random.permutation(k, jnp.arange(1, n, dtype=jnp.int32))
    cycle = jnp.concatenate([jnp.zeros(1, jnp.int32), rest[:-1]])
    succ = jnp.arange(n, dtype=jnp.int32).at[cycle].set(jnp.roll(cycle, -1))
    return succ.reshape(shape)


def successor(seed: int, shape):
    """The chase's int32 successor array (flat ``succ[j]`` follows ``j``).

    Unlike the program's own ``chase_perm``, which is one full cycle, this
    is one random cycle through 0 over all elements but one, which maps to
    itself.  A walk of a multiple of ``n`` steps then ends away from where it
    started, so a walk that was skipped or cut short gives another index."""
    return _successor(key(seed), tuple(shape))


def runner_value(seed: int) -> float:
    """The Runner's buffer fill value from the seed, in [1, 2)."""
    return 1.0 + ((seed * 2654435761) % 2**32) / 2**32
