"""Quantized-weight ops: jit'd wrappers dispatching XLA or Pallas impls.

``qmatmul(x, qt)`` computes ``x @ dequant(qt)``:

  * ``impl="xla"`` (default off-TPU): dequantize with the pure-jnp format
    code and contract — XLA fuses the unpack into the matmul's operand
    pipeline; this is also the path the multi-pod dry-run lowers, so the
    roofline terms include dequant cost.
  * ``impl="pallas"``: the fused dequant-matmul kernels in this package
    (weights stay packed in HBM; per-tile dequant in VMEM; MXU contraction).
    Validated in interpret mode on CPU, targeted at TPU.

Set ``REPRO_KERNEL_IMPL=pallas|xla`` or pass ``impl=`` explicitly.
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp

from ..core.qtensor import QTensor

_DEFAULT_IMPL = os.environ.get("REPRO_KERNEL_IMPL", "xla")

# formats with a fused Pallas kernel (filled in by the kernel modules)
PALLAS_MATMULS: dict = {}


def _register_pallas(fmt: str):
    def deco(fn):
        PALLAS_MATMULS[fmt] = fn
        return fn
    return deco


def qmatmul(x: jax.Array, qt: QTensor, impl: str | None = None) -> jax.Array:
    """x: (..., K) [or (E, ..., K) matching qt's leading dims] -> (..., N)."""
    impl = impl or _DEFAULT_IMPL
    lead = qt.shape[:-2]
    # every quantized weight product runs under one scope, so a device
    # trace can sum the weight path's time (op_name ".../qmatmul/...")
    with jax.named_scope("qmatmul"):
        if impl == "pallas" and qt.fmt in PALLAS_MATMULS and not lead:
            return PALLAS_MATMULS[qt.fmt](x, qt)
        w = qt.dequantize(x.dtype)
        if not lead:
            return jnp.dot(x, w, preferred_element_type=jnp.float32
                           ).astype(x.dtype)
        # batched (expert) weights: leading dims of x must match qt's
        return jnp.einsum("...ck,...kn->...cn", x, w,
                          preferred_element_type=jnp.float32).astype(x.dtype)


def qgather_columns(qt: QTensor, idx: jax.Array) -> jax.Array:
    """Dequantize only columns ``idx`` of a (K, N) QTensor -> (K, *idx.shape).

    Used for embedding lookup: packed fields all carry N last, so a gather
    on the final axis selects the tokens' columns before dequantization —
    the full embedding matrix is never materialised in fp.
    """
    flat = idx.reshape(-1)
    fields = {k: jnp.take(v, flat, axis=-1) for k, v in qt.fields.items()}
    sub = QTensor(fields, qt.fmt, qt.shape[:-1] + (flat.shape[0],))
    w = sub.dequantize(jnp.float32)                     # (K, n_idx)
    return w.reshape(qt.shape[-2], *idx.shape)


def dequantize(qt: QTensor, dtype=jnp.bfloat16) -> jax.Array:
    return qt.dequantize(dtype)
