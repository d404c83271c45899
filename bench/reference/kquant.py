"""The k-quant formats as a plain round trip: float32 weights in, the
float32 weights that the packed format stands for out.

Written from llama.cpp's block formats as this repository's packed layout
defines them (superblocks of 256 along the contraction axis; scales and
mins as integer codes against one float16 ``d`` / ``dmin`` per
superblock; codes rounded half away from zero).  It packs nothing: the
reference needs the values, and a packing fault of the program shows as a
gap between the two.
"""

from __future__ import annotations

import jax.numpy as jnp

# name -> (kind, block, sub-block, code max, scale-code max)
FORMATS = {
    "q2_k": ("asym", 256, 16, 3, 15),
    "q4_k": ("asym", 256, 32, 15, 63),
    "q5_k": ("asym", 256, 32, 31, 63),
    "q3_k": ("sym", 256, 16, 3, 31),
    "q6_k": ("sym", 256, 16, 31, 127),
    "q8_0": ("q8", 32, 32, 127, 0),
}


def _rnd(x):
    return jnp.trunc(x + jnp.where(x >= 0, 0.5, -0.5))


def _inv(x):
    return jnp.where(x != 0, 1.0 / jnp.where(x != 0, x, 1.0), 0.0)


def _f16(x):
    return x.astype(jnp.float16).astype(jnp.float32)


def quant_dequant(w: jnp.ndarray, fmt: str) -> jnp.ndarray:
    """``w`` (K, N) float32 -> the (K, N) float32 values ``fmt`` stores."""
    kind, block, sub, qmax, smax = FORMATS[fmt]
    k, n = w.shape
    pad = (-k) % block
    if pad:
        w = jnp.pad(w, ((0, pad), (0, 0)))
    s = w.shape[0] // block
    nsub = block // sub
    wb = w.reshape(s, nsub, sub, n)
    if kind == "q8":
        d = jnp.max(jnp.abs(wb), axis=2, keepdims=True) / 127.0
        q = jnp.clip(_rnd(wb * _inv(d)), -127, 127)
        out = q * _f16(d)
    elif kind == "asym":
        lo = jnp.minimum(jnp.min(wb, axis=2), 0.0)            # (s, nsub, n)
        hi = jnp.maximum(jnp.max(wb, axis=2), lo)
        scale = (hi - lo) / qmax
        mins = -lo
        d = jnp.max(scale, axis=1, keepdims=True) / smax      # (s, 1, n)
        dmin = jnp.max(mins, axis=1, keepdims=True) / smax
        sc = jnp.clip(_rnd(scale * _inv(d)), 0, smax)
        m = jnp.clip(_rnd(mins * _inv(dmin)), 0, smax)
        eff_s = (d * sc)[:, :, None, :]
        eff_m = (dmin * m)[:, :, None, :]
        q = jnp.clip(_rnd((wb + eff_m) * _inv(eff_s)), 0, qmax)
        out = q * (_f16(d) * sc)[:, :, None, :] \
            - (_f16(dmin) * m)[:, :, None, :]
    else:
        idx = jnp.argmax(jnp.abs(wb), axis=2, keepdims=True)
        top = jnp.take_along_axis(wb, idx, axis=2)[:, :, 0, :]
        scale = top / (-(qmax + 1))
        d = jnp.max(jnp.abs(scale), axis=1, keepdims=True) / smax
        sc = jnp.clip(_rnd(scale * _inv(d)), -(smax + 1), smax)
        eff = (d * sc)[:, :, None, :]
        q = jnp.clip(_rnd(wb * _inv(eff)), -(qmax + 1), qmax)
        out = q * (_f16(d) * sc)[:, :, None, :]
    return out.reshape(s * block, n)[:k]
