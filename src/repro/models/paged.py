"""Paged KV-cache primitives: page pools, gathers and scatters.

A *paged* cache stores each positional cache leaf as a shared pool of
fixed-size pages, ``(num_pages, page_size, *entry_shape)``, instead of a
dense ``(batch, length, *entry_shape)`` block per slot.  A per-slot *block
table* (``(batch, n_logical_pages)`` int32) maps logical page indices to
physical page ids, so memory scales with *live tokens* rather than
``slots x max_len``.

Two physical pages are reserved:

  * ``NULL_PAGE`` (0) — read-only; logical pages a slot has not allocated
    yet point here.  Its ``pos`` entries stay ``-1`` forever so gathered
    entries are masked exactly like unwritten dense-cache entries.
  * ``GARBAGE_PAGE`` (1) — write sink; free decode lanes and padded chunk
    tokens are routed here.  It is never mapped into a live block table,
    so its contents are never read.

Two decode paths read these pools (``kernel=`` on the decode APIs /
``REPRO_PAGED_KERNEL`` env):

  * **fused** (the fast path, default) — the flash-decode Pallas kernels
    in kernels/paged_attn.py attend the pages *in place* through the
    block table with an online softmax; nothing dense is materialised and
    decode bandwidth scales with live pages (the serve loop additionally
    bounds the page loop to the batch's bucketed live horizon).
  * **gather** (the reference implementation) — ``gather_pages`` + slice
    reconstructs the *exact* dense layout so the dense decode/prefill
    math runs unchanged on the gathered view; paged and contiguous are
    bitwise identical by construction (tests/test_paged_cache.py), and
    the fused kernels are checked against this reference to f32 tolerance
    (tests/test_paged_attn_kernel.py).

Chunked prefill still uses the gather path (one gather per admitted
chunk, amortised over the whole chunk — decode was the per-step hot
loop).

**Quantized pools** (``kv_quant="q8_0"`` / ``"q4_0"`` / ``"dq"``): a
positional K/V (or MLA latent) leaf may instead be stored as an int8
pool plus a per-row f32 scale pool (block = the trailing axis; see
``kernels.paged_attn.quantize_kv_page_pool``).  ``q4_0`` packs two
signed 4-bit values per byte along the block axis (the ``*_qs`` pool's
trailing dim is half the row width, which must therefore be even — see
:func:`q4_packed_dim`), cutting page traffic ~8x vs f32.  ``dq`` is the
*dynamic* per-layer policy mirroring ``core/policy.py``'s DQ3_K_M:
sensitive layers (the first/last of the stack, and MLA latents always —
PR 5 measured the MLA+MoE error blow-up) stay ``q8_0`` while the rest
drop to ``q4_0`` (:func:`resolve_layer_quant`).  Writes quantize rows on
the fly (:func:`scatter_token_quant` / :func:`scatter_chunk_quant`),
reads either dequantize inside the fused kernels or through
:func:`gather_pages_quant` for the gather-reference paths.
NULL/GARBAGE reserved-page and last-writer-wins semantics are identical
to the f32 pools (a NULL page's qs and d stay zero, so it dequantizes to
the same never-written zeros — a packed zero byte unpacks to two zero
nibbles).
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from ..kernels.paged_attn import (pack_q4_rows, quantize_kv_page_pool,
                                  quantize_kv_page_pool_q4, unpack_q4_rows)

NULL_PAGE = 0
GARBAGE_PAGE = 1
RESERVED_PAGES = 2

# engine-level cache-quantization specs; "dq" resolves to a per-layer
# mix of the two uniform modes via resolve_layer_quant()
KV_QUANTS = ("q8_0", "q4_0", "dq")
KV_QUANT_MODES = ("q8_0", "q4_0")      # concrete per-leaf storage modes


def check_kv_quant(kv_quant: str | None) -> str | None:
    """Validate a cache-quantization spec (None = f32/model-dtype pools)."""
    if kv_quant and kv_quant not in KV_QUANTS:
        raise ValueError(f"unknown kv_quant {kv_quant!r}; "
                         f"supported: {KV_QUANTS}")
    return kv_quant or None


def q4_packed_dim(width: int, what: str = "row") -> int:
    """Packed (bytes) trailing dim of one q4_0 row of ``width`` values.

    Two nibbles share a byte along the block axis, so the row width must
    be even.  On TPU the *packed* minor dim is what meets the 128-lane
    contract (per shard under ``shard_map``) — interpret mode accepts the
    tiny odd test shapes, as everywhere else in kernels/paged_attn.py.
    """
    if width % 2:
        raise ValueError(
            f"q4_0 requires an even {what} width (two nibbles per byte); "
            f"got {width}")
    return width // 2


class LayerQuant(NamedTuple):
    """Concrete per-layer cache-quantization assignment.

    ``kv``: storage mode for the GQA K/V pools — or, on MLA layers, the
    decoupled-RoPE key pool.  ``latent``: storage mode for the MLA
    ``c_kv`` latent pool (mirrors ``kv`` on non-MLA layers, where it is
    unused).  Values are entries of ``KV_QUANT_MODES``.
    """
    kv: str
    latent: str


def as_layer_quant(kv_quant) -> "LayerQuant | None":
    """Normalize a per-layer spec: a uniform mode string becomes a
    ``LayerQuant`` applying it to every leaf; ``LayerQuant`` (or any
    ``(kv, latent)`` pair) passes through; None stays None."""
    if kv_quant is None:
        return None
    if isinstance(kv_quant, str):
        if kv_quant not in KV_QUANT_MODES:
            raise ValueError(f"not a concrete kv-quant mode: {kv_quant!r} "
                             f"(supported: {KV_QUANT_MODES})")
        return LayerQuant(kv_quant, kv_quant)
    return LayerQuant(*kv_quant)


def dq_sensitive_layers(n_layers: int) -> frozenset:
    """Layers the "dq" policy keeps at q8_0 (the rest drop to q4_0).

    First/last ``max(1, n_layers // 8)`` layers — the related papers'
    finding that low-bit degradation concentrates at the ends of the
    stack.  Degenerate tiny stacks (<= 2 layers) keep every layer
    sensitive, i.e. "dq" == uniform q8_0 there.
    """
    n = max(1, n_layers // 8)
    return frozenset(range(n)) | frozenset(range(max(0, n_layers - n),
                                                 n_layers))


def resolve_layer_quant(kv_quant: str | None, cfg,
                        layer: int) -> LayerQuant | None:
    """Resolve the engine-level ``kv_quant`` spec for one layer.

    Uniform specs ("q8_0"/"q4_0") apply to every leaf.  "dq" assigns
    per-layer bitwidth: sensitive layers (:func:`dq_sensitive_layers`)
    stay q8_0, the rest drop to q4_0 — except MLA ``c_kv`` latents, which
    stay q8_0 on *every* layer (PR 5's measured MLA error blow-up: the
    latent feeds both scores and values, so its error amplifies ~2x a
    K/V perturbation).  Returns None for unquantized caches.
    """
    kv_quant = check_kv_quant(kv_quant)
    if kv_quant is None:
        return None
    if kv_quant != "dq":
        return LayerQuant(kv_quant, kv_quant)
    kv = ("q8_0" if layer in dq_sensitive_layers(cfg.n_layers) else "q4_0")
    return LayerQuant(kv, "q8_0" if cfg.mla else kv)


def pages_for(length: int, page_size: int) -> int:
    """Logical pages needed to cover ``length`` positions."""
    return -(-length // page_size)


def gather_pages(pool: jnp.ndarray, block_table: jnp.ndarray,
                 length: int) -> jnp.ndarray:
    """Reconstruct the dense ``(B, length, ...)`` view of a paged leaf.

    pool: (num_pages, P, ...); block_table: (B, n_pages) int32 with
    ``n_pages * P >= length``.  Unallocated logical pages point at
    ``NULL_PAGE`` and gather its (never written) contents.
    """
    b, n_pages = block_table.shape
    p = pool.shape[1]
    g = pool[block_table]                       # (B, n_pages, P, ...)
    g = g.reshape(b, n_pages * p, *pool.shape[2:])
    return g[:, :length]


def scatter_token(pool: jnp.ndarray, block_table: jnp.ndarray,
                  idx: jnp.ndarray, val: jnp.ndarray,
                  ok: jnp.ndarray | None = None) -> jnp.ndarray:
    """Write one entry per batch row at logical index ``idx`` (B,).

    val: (B, ...).  Rows with ``ok == False`` (non-live decode lanes) are
    routed to ``GARBAGE_PAGE``.  The caller guarantees live rows' logical
    pages are allocated (free lanes' block tables point at
    ``GARBAGE_PAGE`` anyway).
    """
    p = pool.shape[1]
    page = idx // p
    off = idx % p
    phys = jnp.take_along_axis(block_table, page[:, None], axis=1)[:, 0]
    if ok is not None:
        phys = jnp.where(ok, phys, GARBAGE_PAGE)
        off = jnp.where(ok, off, 0)
    return pool.at[phys, off].set(val.astype(pool.dtype))


def scatter_chunk(pool: jnp.ndarray, block_table: jnp.ndarray,
                  idx: jnp.ndarray, val: jnp.ndarray,
                  ok: jnp.ndarray) -> jnp.ndarray:
    """Write a chunk of entries.  idx/ok: (B, C); val: (B, C, ...).

    Entries with ``ok == False`` (padded tokens, superseded ring writes)
    are routed to ``GARBAGE_PAGE`` instead of their mapped page.
    """
    b, c = idx.shape
    p = pool.shape[1]
    page = idx // p
    off = idx % p
    phys = jnp.take_along_axis(block_table, page, axis=1)
    phys = jnp.where(ok, phys, GARBAGE_PAGE)
    off = jnp.where(ok, off, 0)
    flat = val.reshape(b * c, *val.shape[2:]).astype(pool.dtype)
    return pool.at[phys.reshape(-1), off.reshape(-1)].set(flat)


def quantize_rows(val: jnp.ndarray, mode: str):
    """Quantize float rows over the trailing axis in storage ``mode``.

    Returns ``(qs, d)``: int8 values (nibble-packed for q4_0, trailing
    dim halved) and per-row f32 scales.
    """
    if mode == "q8_0":
        return quantize_kv_page_pool(val)
    if mode == "q4_0":
        return quantize_kv_page_pool_q4(val)
    raise ValueError(f"unknown kv-quant mode {mode!r}")


def dequant_rows(qs: jnp.ndarray, d: jnp.ndarray, mode: str) -> jnp.ndarray:
    """Dequantize stored rows back to the f32 view every reader attends."""
    if mode == "q4_0":
        qs = unpack_q4_rows(qs)
    elif mode != "q8_0":
        raise ValueError(f"unknown kv-quant mode {mode!r}")
    return qs.astype(jnp.float32) * d.astype(jnp.float32)[..., None]


def gather_pages_quant(qs_pool: jnp.ndarray, d_pool: jnp.ndarray,
                       block_table: jnp.ndarray, length: int,
                       mode: str = "q8_0") -> jnp.ndarray:
    """Dequantizing :func:`gather_pages` over a quantized leaf pair.

    Returns the dense f32 ``(B, length, ...)`` view ``unpack(qs) * d`` —
    what the prefill-chunk and gather-reference paths attend (the fused
    kernels dequantize the same way, per page tile, without
    materialising this).
    """
    qs = gather_pages(qs_pool, block_table, length)
    d = gather_pages(d_pool, block_table, length)
    return dequant_rows(qs, d, mode)


def scatter_token_quant(qs_pool: jnp.ndarray, d_pool: jnp.ndarray,
                        block_table: jnp.ndarray, idx: jnp.ndarray,
                        val: jnp.ndarray, ok: jnp.ndarray | None = None,
                        mode: str = "q8_0"):
    """Quantize-on-write :func:`scatter_token` for a quantized leaf pair.

    val: (B, ...) float rows; each is quantized per trailing-axis row
    (:func:`quantize_rows`) and the int8 values / f32 scales land in
    their pools under the same routing (``ok`` rows -> GARBAGE_PAGE).
    """
    qs, d = quantize_rows(val, mode)
    return (scatter_token(qs_pool, block_table, idx, qs, ok=ok),
            scatter_token(d_pool, block_table, idx, d, ok=ok))


def scatter_chunk_quant(qs_pool: jnp.ndarray, d_pool: jnp.ndarray,
                        block_table: jnp.ndarray, idx: jnp.ndarray,
                        val: jnp.ndarray, ok: jnp.ndarray,
                        mode: str = "q8_0"):
    """Quantize-on-write :func:`scatter_chunk` for a quantized leaf pair."""
    qs, d = quantize_rows(val, mode)
    return (scatter_chunk(qs_pool, block_table, idx, qs, ok),
            scatter_chunk(d_pool, block_table, idx, d, ok))


def roundtrip_quant(val: jnp.ndarray, mode: str = "q8_0"):
    """Quantize a chunk's rows once: ``(qs, d, dequantized)``.

    ``dequantized`` (``unpack(qs) * d``, f32) is exactly what every later
    read of these rows sees (:func:`gather_pages_quant` and the fused
    quantized kernels compute the same product), so a prefill chunk that
    attends its *own* K/V through this view — and scatters the returned
    ``qs``/``d`` directly via :func:`scatter_chunk`, never quantizing
    twice — reads the same stored values whatever the chunk size:
    in-chunk and cross-chunk reads go through one identical round trip.
    """
    qs, d = quantize_rows(val, mode)
    return qs, d, dequant_rows(qs, d, mode)


# q8_0-specific aliases (the original PR 5 surface; kept because swap /
# parity suites and external callers address the q8 layout by name)

def gather_pages_q8(qs_pool, d_pool, block_table, length):
    return gather_pages_quant(qs_pool, d_pool, block_table, length, "q8_0")


def scatter_token_q8(qs_pool, d_pool, block_table, idx, val, ok=None):
    return scatter_token_quant(qs_pool, d_pool, block_table, idx, val,
                               ok=ok, mode="q8_0")


def scatter_chunk_q8(qs_pool, d_pool, block_table, idx, val, ok):
    return scatter_chunk_quant(qs_pool, d_pool, block_table, idx, val, ok,
                               mode="q8_0")


def roundtrip_q8(val):
    return roundtrip_quant(val, "q8_0")


def extract_pages(pool: jnp.ndarray, page_ids, axis: int = 0) -> jnp.ndarray:
    """Gather whole physical pages ``(n, P, ...)`` for swap-out.

    ``page_ids`` is a host list/array of physical page ids (any leaf kind:
    f32 payload, int8 ``qs``, f32 ``d`` scales, or ``pos`` rows).  The
    returned array is device-side; the caller ``jax.device_get``s it to
    host memory.  Rows are copied verbatim — for q8_0 leaf pairs the int8
    payload and scale rows round-trip bit-exactly, so swap-out/in never
    re-quantizes (see tests/test_kv_quant.py swap-parity oracles).
    ``axis`` is the page axis: 0 for per-layer pools, 1 for scan-stacked
    pools shaped ``(layers, num_pages, ...)``.
    """
    ids = jnp.asarray(page_ids, jnp.int32)
    return pool[ids] if axis == 0 else pool[:, ids]


def inject_pages(pool: jnp.ndarray, page_ids, rows,
                 axis: int = 0) -> jnp.ndarray:
    """Scatter saved page rows back into (possibly different) physical ids.

    Inverse of :func:`extract_pages`: ``rows`` has the same trailing shape
    as one page slice of ``pool``; ``page_ids`` must be freshly allocated
    pages (never NULL/GARBAGE — the reserved invariants are the caller's
    to keep).  ``axis`` is the page axis, as in :func:`extract_pages`.
    """
    ids = jnp.asarray(page_ids, jnp.int32)
    rows = jnp.asarray(rows, pool.dtype)
    return (pool.at[ids].set(rows) if axis == 0
            else pool.at[:, ids].set(rows))


def chunk_write_plan(idx: jnp.ndarray, valid: jnp.ndarray, length: int):
    """Resolve duplicate in-chunk writes to the same logical index.

    idx: (B, C) logical target per token; valid: (B, C) real (non-padded)
    tokens.  Returns ``ok`` (B, C): valid tokens that are the *last* writer
    of their logical index — earlier writers are dropped, matching the
    dense ring-buffer semantics where later positions evict earlier ones.
    (Duplicates only arise for ring targets when a chunk spans more than
    one ring revolution.)
    """
    b, c = idx.shape
    j = jnp.arange(c, dtype=jnp.int32)[None, :]
    marker = jnp.where(valid, j, -1)
    safe_idx = jnp.where(valid, idx, 0)
    bidx = jnp.arange(b)[:, None]
    last = jnp.full((b, length), -1, jnp.int32).at[bidx, safe_idx].max(marker)
    return valid & (jnp.take_along_axis(last, safe_idx, axis=1) == j)
