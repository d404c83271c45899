"""Seeded weights, drawn the same way for the program and for the plain
reference.

Every tensor has its own key, folded from the run's seed, its layer and its
name, and a matrix is drawn in column chunks of ``COLS`` so that the
largest one (the 32B embedding, 5120 x 152064) never exists whole in
float32.  The quantization formats work column by column, so a matrix
quantized chunk by chunk is the matrix quantized whole.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

COLS = 16384
NAMES = ("token_embd", "output", "output_norm", "attn_norm", "ffn_norm",
         "q_proj", "k_proj", "v_proj", "o_proj", "q_bias", "k_bias",
         "v_bias", "gate", "up", "down")
VECTOR_STD = 0.1


def root_key(seed: int) -> jax.Array:
    """A key for any whole-number seed, also past 32 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def tensor_key(root: jax.Array, layer: int | None, name: str) -> jax.Array:
    k = jax.random.fold_in(root, 0 if layer is None else layer + 1)
    return jax.random.fold_in(k, NAMES.index(name))


def column_chunks(n: int) -> list[tuple[int, int]]:
    return [(c, min(c + COLS, n)) for c in range(0, n, COLS)]


def matrix_std(name: str, fan_in: int, residual_layers: int) -> float:
    """1/sqrt(fan_in); the two projections that write into the residual
    stream are further scaled by 1/sqrt(2 L), as deep models are
    initialised, so that the stream does not grow with depth."""
    std = fan_in ** -0.5
    if name in ("o_proj", "down"):
        std /= math.sqrt(2 * residual_layers)
    return std


def chunk_key(key: jax.Array, i: int) -> jax.Array:
    return jax.random.fold_in(key, i)


@partial(jax.jit, static_argnums=(1, 2))
def normal(key: jax.Array, shape: tuple[int, ...], std: float) -> jax.Array:
    return jax.random.normal(key, shape, jnp.float32) * std


@partial(jax.jit, static_argnums=(1,))
def vector(key: jax.Array, n: int) -> jax.Array:
    """A bias, or a norm weight stored as its offset from 1, in bfloat16
    (the type both are served in)."""
    return (jax.random.normal(key, (n,), jnp.float32)
            * VECTOR_STD).astype(jnp.bfloat16)
