"""Shared model primitives: quant-aware linear, norms, RoPE, softcap.

``linear`` transparently accepts either a plain ``jax.Array`` weight or a
packed :class:`~repro.core.qtensor.QTensor`; quantized weights dispatch to
``repro.kernels.ops.qmatmul`` (XLA dequant-matmul by default, Pallas kernel
on TPU).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.qtensor import QTensor


def as_array(w, dtype=jnp.float32) -> jax.Array:
    """Materialise a (possibly quantized) weight as a dense array."""
    if isinstance(w, QTensor):
        return w.dequantize(dtype)
    return w.astype(dtype)


def linear(w, x: jax.Array, bias=None, *, precision=None) -> jax.Array:
    """``y = x @ w (+ bias)`` for fp or quantized ``w``; x: (..., K).

    Output dtype == input dtype (bf16 in the hot path): TPU MXUs accumulate
    in f32 internally regardless, and emitting bf16 halves the bytes of the
    tensor-parallel partial-sum all-reduces that XLA inserts after
    row-parallel matmuls (measured 2x collective reduction —
    EXPERIMENTS.md §Perf).
    """
    if isinstance(w, QTensor):
        from ..kernels import ops
        y = ops.qmatmul(x, w)
    else:
        y = jnp.dot(x, w.astype(x.dtype), precision=precision)
    if bias is not None:
        y = y + bias.astype(y.dtype)
    return y


def gather_heads(x: jax.Array, mesh) -> jax.Array:
    """``x`` replicated on ``mesh`` (returned as is without one).

    Attention leaves its output split over heads on the ``model`` axis
    (the fused kernels run head-split under ``shard_map``, and GSPMD
    splits the gather paths the same way after the head-sharded pools).
    Fed so to ``o_proj``, whose contraction runs over the heads, the
    partitioner computes per-device partial sums and all-reduces them:
    the sum is taken in another order than on one device, and the
    logits drift.  Gathering the heads first keeps every device's
    contraction whole, as ``Engine(mesh=...)`` promises.
    """
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()))


def embed_lookup(w, tokens: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    """Embedding lookup; ``w`` is (d_model, vocab) (blocks along d_model)."""
    if isinstance(w, QTensor):
        from ..kernels import ops
        return ops.qgather_columns(w, tokens).astype(dtype)
    return jnp.take(w, tokens, axis=1).astype(dtype)  # (d, ...) -> move axis
    # note: callers expect (..., d); see embed() below


def embed(w, tokens: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    """Token embedding -> (..., d_model)."""
    e = embed_lookup(w, tokens, dtype)       # (d, *tokens.shape)
    return jnp.moveaxis(e, 0, -1)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dt)


def softcap(x: jax.Array, cap: float) -> jax.Array:
    if not cap:
        return x
    return cap * jnp.tanh(x / cap)


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotate (..., T, H, hd) at absolute ``positions`` (..., T)."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)                      # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., T, hd/2)
    cos = jnp.cos(angles)[..., None, :]                      # (..., T, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def swiglu(gate: jax.Array, up: jax.Array) -> jax.Array:
    return jax.nn.silu(gate.astype(jnp.float32)).astype(gate.dtype) * up


def geglu(gate: jax.Array, up: jax.Array) -> jax.Array:
    return jax.nn.gelu(gate.astype(jnp.float32), approximate=True).astype(
        gate.dtype) * up


def ffn_apply(p: dict, x: jax.Array, act: str = "swiglu") -> jax.Array:
    """SwiGLU/GeGLU FFN from a param subview with gate/up/down."""
    g = linear(p["gate"], x)
    u = linear(p["up"], x)
    h = swiglu(g, u) if act == "swiglu" else geglu(g, u)
    return linear(p["down"], h)
