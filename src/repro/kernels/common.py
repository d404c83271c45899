"""Shared scaffold for fused dequant-matmul Pallas TPU kernels.

Kernel shape (one grid step): ``y[bm, bn] += x[bm, bk] @ dequant(tile)``
where the packed tile covers ``bk = g * block`` contraction rows (``g``
superblocks) of one output-column block.  Weights stream HBM->VMEM packed
(bpw/16 of the bf16 bytes); dequantisation happens on the VPU into a
(bk, bn) f32 tile that feeds the MXU.  Grid: (M/bm, N/bn, S/g) with the
contraction dim innermost so the output block stays resident in VMEM
(revisiting-accumulate pattern).

Block sizes default to MXU-aligned (bm=128, bn=128, g s.t. bk=256); the perf
pass (EXPERIMENTS.md §Perf) tunes them per shape.

On CPU the kernels run with ``interpret=True`` (pure-Python execution of the
kernel body) — the validation mode used by the test suite; TPU is the
deployment target.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.formats import FORMATS
from ..core.qtensor import QTensor


def _interpret_default() -> bool:
    """Interpret the Pallas kernels exactly where Mosaic cannot compile
    them: on a CPU backend."""
    return jax.default_backend() == "cpu"


# --- unpack helpers on (g, X, bn) tiles, expanding along axis -2 -----------

def i32(x: jax.Array) -> jax.Array:
    return x.astype(jnp.int32)


def expand_nibbles(b: jax.Array) -> jax.Array:
    """(g, H, bn) bytes -> (g, 2H, bn) values in [0,16) (i32)."""
    b = i32(b)
    return jnp.concatenate([b & 0x0F, (b >> 4) & 0x0F], axis=-2)


def expand_2bit(b: jax.Array) -> jax.Array:
    b = i32(b)
    return jnp.concatenate([(b >> (2 * p)) & 0x03 for p in range(4)], axis=-2)


def expand_1bit(b: jax.Array) -> jax.Array:
    b = i32(b)
    return jnp.concatenate([(b >> p) & 0x01 for p in range(8)], axis=-2)


def expand_sub(vals: jax.Array, sub: int) -> jax.Array:
    """(g, nsub, bn) per-sub-block values -> (g, nsub*sub, bn) broadcast."""
    g, nsub, bn = vals.shape
    return jnp.broadcast_to(vals[:, :, None, :], (g, nsub, sub, bn)).reshape(
        g, nsub * sub, bn)


def flatten_k(tile: jax.Array) -> jax.Array:
    """(g, B, bn) -> (g*B, bn) in superblock-major contraction order."""
    g, b, bn = tile.shape
    return tile.reshape(g * b, bn)


def _pick_g(s: int, target_bk: int, block: int) -> int:
    want = max(1, target_bk // block)
    g = min(want, s)
    while s % g:
        g -= 1
    return g


def build_qmatmul(fmt: str, field_layout: dict[str, tuple],
                  dequant_tile: Callable, *, target_bk: int = 256):
    """Create the jit-able fused matmul for one format.

    ``field_layout``: field name -> per-superblock shape suffix
    (e.g. q4_k: {"qs": (128,), "scales": (8,), "mins": (8,), "d": (),
    "dmin": ()}); every field is stored ``(S, *suffix, N)``.
    ``dequant_tile(tiles) -> (bk, bn) f32`` given tiles ``(g, *suffix, bn)``.
    """
    block = FORMATS[fmt].block

    def qmatmul(x: jax.Array, qt: QTensor, *, bm: int = 128, bn: int = 128,
                target_bk: int = target_bk,
                interpret: bool | None = None) -> jax.Array:
        assert qt.fmt == fmt, (qt.fmt, fmt)
        assert not qt.shape[:-2], "pallas path is for unbatched weights"
        *lead, m, k = x.shape
        k_logical, n = qt.shape[-2], qt.shape[-1]
        assert k == k_logical, (x.shape, qt.shape)
        x2 = x.reshape(-1, k)
        m_flat = x2.shape[0]
        s = qt.num_superblocks
        k_pad = s * block
        if k_pad != k:
            x2 = jnp.pad(x2, ((0, 0), (0, k_pad - k)))
        bm_eff = min(bm, max(8, m_flat))
        m_pad = -(-m_flat // bm_eff) * bm_eff
        if m_pad != m_flat:
            x2 = jnp.pad(x2, ((0, m_pad - m_flat), (0, 0)))
        bn_eff = min(bn, n)
        assert n % bn_eff == 0, (n, bn_eff)
        g = _pick_g(s, target_bk, block)
        bk = g * block

        grid = (m_pad // bm_eff, n // bn_eff, s // g)
        fields = [qt.fields[name] for name in field_layout]

        def kernel(x_ref, *refs):
            o_ref = refs[-1]
            f_refs = refs[:-1]

            @pl.when(pl.program_id(2) == 0)
            def _init():
                o_ref[...] = jnp.zeros_like(o_ref)

            tiles = {name: r[...] for name, r in zip(field_layout, f_refs)}
            w = dequant_tile(tiles)                     # (bk, bn) f32
            o_ref[...] += jnp.dot(
                x_ref[...].astype(jnp.float32), w,
                preferred_element_type=jnp.float32)

        in_specs = [pl.BlockSpec((bm_eff, bk), lambda i, j, kk: (i, kk))]
        for name, suffix in field_layout.items():
            blk = (g,) + suffix + (bn_eff,)
            nsfx = len(suffix)

            def idx(i, j, kk, _n=nsfx):
                return (kk,) + (0,) * _n + (j,)

            in_specs.append(pl.BlockSpec(blk, idx))

        out = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((bm_eff, bn_eff), lambda i, j, kk: (i, j)),
            out_shape=jax.ShapeDtypeStruct((m_pad, n), jnp.float32),
            interpret=(_interpret_default() if interpret is None
                       else interpret),
        )(x2, *fields)
        out = out[:m_flat].reshape(*lead, m, n)
        return out.astype(x.dtype)

    qmatmul.__name__ = f"qmatmul_{fmt}"
    return qmatmul
