"""Known-good: static declarations and data-only dynamic args."""

from functools import partial

import jax
import jax.numpy as jnp


@partial(jax.jit, static_argnames=("n", "steps", "flag"))
def static_everything(x, n, steps, flag):
    out = jnp.zeros((n, 4))
    for _ in range(steps):
        out = out + x
    if flag:
        out = -out
    return out


@partial(jax.jit, static_argnames=("opts",))
def hashable_default(x, opts=()):
    # tuples hash: a fine default for a static argument
    return x * 2.0 if opts else x


@jax.jit
def dynamic_data_ok(x, y):
    # dynamic args used as *data* (not shape/bound/branch) are the point
    return x @ y + jnp.ones((8, 128))


@jax.jit
def broadcast_data_ok(x):
    # broadcast_to's first argument is data; its second is the shape
    return jnp.broadcast_to(x[:8, None], (8, 4))


def not_jitted(x, n):
    # no jit decorator: Python bounds are concrete
    for _ in range(n):
        x = x * 2.0
    return x
