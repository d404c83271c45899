"""Fused Pallas paged-attention decode kernels (flash-decode over pages).

One decode step attends a single query row per slot against that slot's KV
pages **in place**: the slot's block table rides in as a scalar-prefetch
operand, and a running (max, sum-exp, accumulator) online softmax folds the
pages together — no ``(B, max_len, ...)`` dense view is ever materialised.
Decode bandwidth scales with *live* pages, not ``slots x max_len``:

  * GQA decode (:func:`_attn_core`) runs over ``(slot, block)``.  A block
    is ``ppb = min(nj, 256 // page_size)`` pages (about ``_BLOCK_TOKENS``
    tokens), each copied by its own DMA into a double-buffered VMEM block,
    so one grid step does one MXU product over some 256 tokens instead of
    one page.  A lane's blocks past its live pages are neither copied nor
    computed, and each step prefetches the next live block in grid order.
  * MLA decode and chunked prefill run over ``(slot, logical_page)``, one
    physical page per step (``BlockSpec`` index map ``block_table[slot,
    page]``); unallocated logical pages all map to the NULL page, so
    consecutive trailing grid steps revisit one resident block.

Two kernel scaffolds — GQA (:func:`_attn_core`) and absorbed MLA
(:func:`_mla_core`) — are each parameterized over a K/V *tile loader*
(plain f32 pages, or int8+per-row-scale pages dequantised on the VPU:
q8_0, or nibble-packed q4_0 unpacked with arithmetic shifts), so one
score/mask/online-softmax body serves all the public decode entries:

  * :func:`paged_attn_decode` — GQA/MHA over K/V/pos pools, full horizon or
    sliding window (``window > 0``); the validity mask comes from the
    page's ``pos`` entries, so ring wraparound needs no special casing.
  * :func:`paged_attn_decode_quant` — the same attention over quantized
    K/V pools (int8 values + one f32 scale per (token, head) row, block =
    ``head_dim``; q4_0 packs two int4 values per byte), the fast path
    behind ``Engine(kv_quant=...)``: pages stream in packed and
    dequantisation happens inside the online-softmax loop, cutting decode
    page traffic ~4x (q8_0) / ~7x (q4_0) vs f32 pools.
    :func:`paged_attn_decode_q8` is the mode-pinned q8_0 alias.
  * :func:`paged_mla_decode` — absorbed MLA over latent/rope pools; scores
    and the output both live in latent space (the ``kv_b`` projection is
    folded in by the caller), validity is positional (``idx <= pos``).
  * :func:`paged_mla_decode_quant` — absorbed MLA over quantized
    latent/rope pools (one scale per (token,) row, block = the
    latent/rope width); the latent and rope leaves may carry *different*
    modes (the "dq" per-layer policy keeps MLA latents q8_0 while rope
    keys drop to q4_0).  :func:`paged_mla_decode_q8` pins both to q8_0.

The same scaffolds extend to *chunked prefill*:
:func:`paged_attn_prefill_quant` / :func:`paged_mla_prefill_quant` attend
a whole (B, C)-token chunk against the quantized pools **after** the
chunk's rows were quantized once and scattered into their pages
(write-then-attend).  The grid is the same ``(slot, logical_page)``; each
step scores all C chunk queries against one page tile, with a per-row
``(C, P)`` validity mask (written ∧ causal ∧ ``logical_idx <= qpos`` —
the logical-index term keeps stale rows beyond a lane's frontier out even
when their stored positions look plausible).  This closes the last dense
dequant: packed pages stay packed end to end, and the page enumeration
order is independent of the chunk split, so every chunk split attends the
same stored rows in the same order.  The Pallas kernel's outputs are then
bitwise chunk-size invariant; the XLA twin's, and the model's projections
around either, are not: XLA blocks a dot or a reduction by its shape, so
splits differ in the last bits (tests/test_kv_dynamic.py holds the decode
logits after chunked prefill to a stated tolerance).  Ring layers keep
the gather path.

``active_pages`` bounds the page loop: the serving engine knows the
largest live horizon across its lanes each iteration and passes a bucketed
page count, so a 4-token batch in a 32k-context pool touches one page per
slot, not 2048.  Callers must guarantee every live key sits inside the
first ``active_pages`` logical pages (the engine buckets
``pages_for(max_pos + 1)`` up to a power of two).

Each family has two implementations of the *same* page-bounded algorithm,
selected by ``impl`` (or the ``REPRO_PAGED_IMPL`` env: auto | pallas |
xla):

  * ``"pallas"`` — the fused kernel above; the deployment target on TPU,
    validated on CPU in interpret mode by tests/test_paged_attn_kernel.py
    (kernels/common.py semantics).  Interpret execution pays ~ms per grid
    step, so it is a correctness mode, not a performance mode.
  * ``"xla"`` — gathers **only the first ``active_pages`` logical pages**
    (``pool[block_table[:, :n]]``, a bounded gather) and runs one masked
    softmax over them.  Bytes touched still scale with live tokens — this
    is the fast path on hosts without Mosaic, and what ``"auto"`` picks
    whenever the Pallas default would be interpret mode.

Every entry point takes ``mesh=None`` (``Engine(mesh=...)`` threads the
serving mesh through): with a mesh the Pallas path runs under
``shard_map`` — head-parallel when the (kv-)head axis divides the
``model`` mesh axis (each device attends its own head slice of the page
pools; heads are independent, so there are no collectives), fully
replicated otherwise — while the XLA twin stays a plain jit body and
lets GSPMD partition the bounded gather over sharded pool operands.

For full MXU/VPU utilisation on TPU, the one-page-per-step kernels (MLA,
prefill) want ``page_size`` a multiple of 128 and head counts multiples of
8; GQA decode's blocks reach 256 tokens from any page size.  The tests
intentionally use tiny odd pages, which interpret mode accepts.  Under
``shard_map`` the 128-lane alignment contract applies to the *per-shard*
shapes (global dim / mesh-axis size), which is what the pallas-contract
lint rule checks.
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import _interpret_default

NEG_INF = -2.0e38
_LANES = 128          # VPU lane width: scratch minor dim

PAGED_IMPL_ENV = "REPRO_PAGED_IMPL"


def _resolve_impl(impl: str | None) -> str:
    impl = impl or os.environ.get(PAGED_IMPL_ENV, "auto")
    if impl == "auto":
        # interpret-mode Pallas is a validation harness (ms per grid
        # step); hosts that would interpret get the bounded-gather XLA
        # twin of the same algorithm instead
        return "xla" if _interpret_default() else "pallas"
    if impl not in ("pallas", "xla"):
        raise ValueError(f"unknown paged-attention impl {impl!r}")
    return impl


def _n_active(block_table: jax.Array, active_pages: int | None) -> int:
    n_pages = block_table.shape[1]
    if active_pages is None:
        return n_pages
    return max(1, min(int(active_pages), n_pages))


def _lane_bound(lane_pages: jax.Array | None, b: int, nj: int) -> jax.Array:
    """Per-lane live-page counts, clamped into ``[1, nj]``.

    ``None`` degrades to the batch-wide bound ``nj`` for every lane, so
    the kernels always run the same (lane-clamped) code path.
    """
    if lane_pages is None:
        return jnp.full((b,), nj, jnp.int32)
    return jnp.clip(lane_pages.astype(jnp.int32), 1, nj)


# The GQA decode kernel attends a lane's pages a block at a time: about
# _BLOCK_TOKENS tokens per grid step, enough keys that one step's copies and
# products outweigh its fixed cost.

_BLOCK_TOKENS = 256


def _pages_per_block(page_size: int, nj: int) -> int:
    """Pages per block: about ``_BLOCK_TOKENS`` tokens, at most the
    bucket's ``nj``."""
    return min(nj, max(1, _BLOCK_TOKENS // page_size))


def _block_pages(live, blk, ppb):
    """The fetch plan: how many pages of block ``blk`` a lane with ``live``
    live pages copies — its live pages in that block, from the block's
    first on; 0 for a block past them.  Python ints or traced scalars."""
    return jnp.minimum(jnp.maximum(live - blk * ppb, 0), ppb)


def _next_block(lane, blk, live, ppb):
    """Grid position ``(lane, blk)`` of the live block after ``(lane,
    blk)``: the lane's next block while it has live pages, else the next
    lane's first (every lane has a live page)."""
    more = (blk + 1) * ppb < live
    return jnp.where(more, lane, lane + 1), jnp.where(more, blk + 1, 0)


def _page_rows(x: jax.Array, paired: bool) -> jax.Array:
    """(num_pages, P, Hkv, W) K/V leaf -> (num_pages, rows, W') view the
    kernel copies a page at a time: one row per (token, kv head) in storage
    order, so the view of a row-major pool costs nothing; ``paired`` puts
    two consecutive rows side by side."""
    n, tp, hkv, w = x.shape
    if paired:
        return x.reshape(n, tp * hkv // 2, 2 * w)
    return x.reshape(n, tp * hkv, w)


def _column_order(n: int, paired: bool) -> np.ndarray:
    """Storage index (token-major, kv head minor) of each of a block's
    ``n`` key rows in the kernel's column order: paired q4_0 rows unpack
    to every even row, then every odd one."""
    idx = np.arange(n)
    return np.concatenate([idx[0::2], idx[1::2]]) if paired else idx


def _block_columns(x: jax.Array, b: int, nb: int, cols: int,
                   paired: bool) -> jax.Array:
    """Per-key side data gathered through the block table, (B, nb * ppb,
    P, Hkv) -> (B, 1, nb * cols), each block's columns in kernel order."""
    x = x.reshape(b, nb, cols)
    if paired:
        x = x[..., _column_order(cols, paired)]
    return x.reshape(b, 1, nb * cols)


def _block_values(raw: jax.Array, quant, paired: bool) -> jax.Array:
    """(ppb, rows, W') copied pages -> (cols, D) key or value rows in
    column order.  Quantized rows come back as f32 integers: their row
    scales multiply the score and probability columns instead."""
    v = raw.reshape(-1, raw.shape[-1])
    if quant is None:
        return v
    v = raw.astype(jnp.int32).reshape(v.shape)
    if quant == "q4_0":
        w = v.shape[-1] // 2
        v = (jnp.concatenate([_q4_values(v[:, :w]), _q4_values(v[:, w:])])
             if paired else _q4_values(v))
    return v.astype(jnp.float32)


# Pallas TPU blocks must match the (8, 128) tiling in their last two dims
# or span them whole, so a per-token (num_pages, P) leaf cannot be tiled
# one page at a time.  The kernels view it with a unit axis instead: a
# (1, P) row where it masks score columns (positions), a (P, 1) column
# where it scales tile rows (MLA dequant scales).

def _row_leaf(x: jax.Array) -> jax.Array:
    return x.reshape(x.shape[0], 1, x.shape[1])


def _col_leaf(x: jax.Array) -> jax.Array:
    return x.reshape(*x.shape, 1)


def _key_index(tp: int) -> jax.Array:
    """(1, P) logical key indices of the page tile at grid step
    ``(slot, j)``."""
    return (pl.program_id(1) * tp
            + jax.lax.broadcasted_iota(jnp.int32, (1, tp), 1))


_MAX_ROWS = 512        # query rows per chunked-prefill grid step


def _row_block(rows: int, cap: int) -> int:
    """Largest divisor of ``rows`` that is at most ``cap`` and a multiple
    of 8 (the sublane tiling), or ``rows`` itself when small."""
    if rows <= cap:
        return rows
    for rb in range(cap - cap % 8, 7, -8):
        if rows % rb == 0:
            return rb
    return rows


def _kernel_name(*parts) -> str:
    """The ``name=`` of a Pallas call, as a device trace shows it: family
    and phase, table kind, then storage (a pool dtype, a quant mode, or
    MLA's pair of modes), e.g. ``paged_attn_decode_full_bfloat16`` or
    ``paged_mla_prefill_q8_0_q4_0``."""
    words = ["paged"]
    for p in parts:
        if isinstance(p, tuple):
            words += p
        else:
            words.append(p if isinstance(p, str) else jnp.dtype(p).name)
    return "_".join(words)


def _finish(o_ref, acc_ref, l_ref, nj: int):
    """Write the normalised accumulator on the last page step."""

    @pl.when(pl.program_id(1) == nj - 1)
    def _():
        l = jnp.maximum(l_ref[:, 0:1], 1e-30)
        o_ref[0] = (acc_ref[...] / l).reshape(o_ref.shape[1:])


def _online_update(s, valid, v_tile, m_ref, l_ref, acc_ref):
    """One page tile of the running softmax.  s: (rows, P) f32 masked
    scores (NEG_INF where invalid); valid: (P,) bool shared by every row,
    or (rows, P) per-row (the chunked-prefill kernels, where each query
    row sits at its own position); v_tile(p) -> (rows, Dv) given the
    probability tile."""
    m_prev = m_ref[:, 0:1]
    l_prev = l_ref[:, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    # NEG_INF is a finite sentinel: exp(s - m_new) is 1, not 0, for fully
    # masked tiles — mask the probabilities explicitly instead
    mask = valid if valid.ndim == s.ndim else valid[None, :]
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = jnp.broadcast_to(l_prev * corr + p.sum(1, keepdims=True),
                                  l_ref.shape)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    acc_ref[...] = acc_ref[...] * corr + v_tile(p)


def _init_accumulators(m_ref, l_ref, acc_ref):
    @pl.when(pl.program_id(1) == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)


# ---------------------------------------------------------------------------
# GQA / MHA over K/V/pos page pools (f32 or q8_0 leaves)
# ---------------------------------------------------------------------------

def paged_attn_decode(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                      pos_pool: jax.Array, block_table: jax.Array,
                      pos: jax.Array, *, window: int = 0,
                      softcap: float = 0.0, scale: float | None = None,
                      active_pages: int | None = None,
                      lane_pages: jax.Array | None = None,
                      impl: str | None = None,
                      interpret: bool | None = None,
                      mesh=None) -> jax.Array:
    """Fused one-token paged GQA decode.

    q: (B, H, D) query row per slot (RoPE already applied, unscaled);
    k_pool/v_pool: (num_pages, P, Hkv, D[v]); pos_pool: (num_pages, P)
    int32 absolute positions (-1 = unwritten); block_table: (B, n_pages)
    int32; pos: (B,) int32 current absolute position.  A key at stored
    position ``t`` is attendable iff ``0 <= t <= pos`` and, when
    ``window > 0``, ``t > pos - window``.  ``lane_pages`` (B,) int32
    optionally bounds each lane's page loop to its *own* live page count
    (pages past it are never copied, so a short lane's reads do not scale
    with the batch-max bound).  Every live key must sit inside the first
    ``lane_pages[i]`` logical pages.  Returns (B, H, Dv) f32.
    """
    nj = _n_active(block_table, active_pages)
    return _attn_core(
        q, (k_pool, v_pool), pos_pool, block_table, pos,
        _lane_bound(lane_pages, q.shape[0], nj),
        window=window, softcap=softcap,
        scale=(q.shape[-1] ** -0.5 if scale is None else scale),
        nj=nj, ppb=_pages_per_block(k_pool.shape[1], nj),
        impl=_resolve_impl(impl),
        interpret=(_interpret_default() if interpret is None else interpret),
        quant=None, mesh=mesh)


def _dequant(qs: jax.Array, d: jax.Array, mode: str) -> jax.Array:
    """Dequantize one tile/leaf: int8 values x per-row f32 scale.

    ``mode="q4_0"`` first unpacks two int4 nibbles per byte
    (:func:`unpack_q4_rows`) — the trailing axis doubles.  This is the
    in-kernel tile loader *and* the bounded-gather dequant, so the two
    impls see bit-identical f32 values.
    """
    return _dequant_rows(qs, d[..., None], mode)


def _dequant_rows(qs: jax.Array, d_col: jax.Array, mode: str) -> jax.Array:
    """:func:`_dequant` with the scales already carrying the trailing unit
    axis (``d_col``: (..., 1)) — the MLA kernels load their per-token
    scales as a (P, 1) column and so broadcast them with no shape cast."""
    if mode == "q4_0":
        qs = _q4_values(qs)
    return qs.astype(jnp.float32) * d_col.astype(jnp.float32)


def _gathered_kv(kv: tuple, btj: jax.Array, quant):
    """Bounded gather of the K/V leaves through ``btj`` logical pages —
    f32, dequantised in the gathered (page-bounded) layout when ``quant``
    so only the live pages are ever expanded.  ``quant`` is ``None``
    (f32 leaves), a mode string shared by both leaves, or a per-leaf
    ``(mode_a, mode_b)`` pair (MLA latent/rope under the "dq" policy)."""
    if quant:
        ma, mb = (quant, quant) if isinstance(quant, str) else quant
        aq, ad, bq, bd = kv
        return (_dequant(aq[btj], ad[btj], ma),
                _dequant(bq[btj], bd[btj], mb))
    return tuple(x[btj].astype(jnp.float32) for x in kv)


def _xla_attn(q, ks, vs, ps, pos, *, window, softcap, scale):
    """Bounded-gather XLA twin: one masked softmax over the gathered pages
    (grouped einsum — KV stays in its (Hkv,) layout)."""
    b, h, d = q.shape
    hkv, dv = ks.shape[2], vs.shape[-1]
    rep = h // hkv
    qg = (q.astype(jnp.float32) * scale).reshape(b, hkv, rep, d)
    s = jnp.einsum("bkrd,blkd->bkrl", qg, ks,
                   preferred_element_type=jnp.float32)
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    valid = (ps >= 0) & (ps <= pos[:, None])
    if window:
        valid &= ps > pos[:, None] - window
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkrl,blkd->bkrd", w, vs,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, h, dv)


@partial(jax.jit, static_argnames=("window", "softcap", "scale", "nj",
                                   "ppb", "impl", "interpret", "quant",
                                   "mesh"))
def _attn_core(q, kv, pos_pool, block_table, pos, lane_pages, *,
               window: int, softcap: float, scale: float, nj: int,
               ppb: int, impl: str, interpret: bool, quant: str | None,
               mesh=None) -> jax.Array:
    """Shared GQA flash-decode scaffold.  ``kv`` is ``(k_pool, v_pool)``
    (``quant=None``) or ``(k_qs, k_d, v_qs, v_d)`` with ``quant`` naming
    the storage mode ("q8_0" | "q4_0" — q4 leaves are nibble-packed, so
    their trailing axis is half the head dim); the
    score/mask/online-softmax body is identical — only the block loader
    changes (pages as stored, or int8 values with an arithmetic-shift
    nibble unpack first for q4_0, whose row scales multiply the score and
    probability columns).

    The grid is ``(slot, block)``, a block being ``ppb`` logical pages.
    Each page of a block is one DMA of its ``(P * Hkv, D)`` rows (token
    major, kv head minor: the pool's own order) into a double-buffered
    VMEM block, so one product scores every query head against the
    block's ``ppb * P * Hkv`` key rows; a constant head mask keeps each
    query head to its group's columns.  ``lane_pages`` (B,) int32 in
    ``[1, nj]`` bounds each lane: blocks past its live pages are neither
    fetched nor computed, and in its last live block only its live pages
    are fetched (:func:`_block_pages`).  Each live step starts the copies
    of the next live block in grid order (:func:`_next_block`), the next
    lane's first included.  Positions (and quantized scales) are gathered
    through the block table beforehand, lane-major, with ``-1`` past the
    lane's live pages, like the XLA twin's.

    ``mesh`` (static): run the Pallas path under ``shard_map`` on it —
    head-parallel when the kv-head axis divides the ``model`` axis, fully
    replicated otherwise.  The XLA twin ignores it (GSPMD partitions the
    bounded gather over sharded operands under the caller's jit).
    """
    b, h, d = q.shape
    tp, hkv = kv[0].shape[1], kv[0].shape[2]
    dv = (kv[2] if quant else kv[1]).shape[-1]
    if quant == "q4_0":
        dv *= 2                     # packed leaf: two values per byte
    if impl == "xla":
        btj = block_table[:, :nj]
        ks, vs = _gathered_kv(kv, btj, quant)
        ps = pos_pool[btj]                                   # (B, nj, P)
        # out-of-lane pages read as unwritten (pos = -1), as in the
        # fused kernel
        ps = jnp.where(jnp.arange(nj)[None, :, None] < lane_pages[:, None,
                                                                  None],
                       ps, -1)
        return _xla_attn(
            q, ks.reshape(b, nj * tp, hkv, d), vs.reshape(b, nj * tp, hkv, dv),
            ps.reshape(b, nj * tp), pos,
            window=window, softcap=softcap, scale=scale)

    def shard_run(block_table, pos, lane_pages, q, *rest):
        """Build + invoke the pallas_call.  Shapes derive from the
        operands, which are *per-shard* inside shard_map — so the kernel,
        the page views and scratch all see the local head slice."""
        *kv_ops, pos_pool = rest
        b, h, d = q.shape
        tp, hkv = kv_ops[0].shape[1], kv_ops[0].shape[2]
        dv = (kv_ops[2] if quant else kv_ops[1]).shape[-1]
        if quant == "q4_0":
            dv *= 2
        rep = h // hkv
        nb = -(-nj // ppb)
        cols = ppb * tp * hkv           # key rows of one block, all heads
        # a packed q4_0 row is half a head wide, and the chip copies whole
        # 128-lane rows: copy its pages two key rows to a row
        paired = quant == "q4_0" and (tp * hkv) % 2 == 0
        vals = (kv_ops[0], kv_ops[2]) if quant else tuple(kv_ops)
        k_rows, v_rows = (_page_rows(x, paired) for x in vals)

        # per-key side data, lane-major in the kernel's column order:
        # stored positions and, for quantized pools, the K and V row
        # scales; past each lane's live pages they read -1 and 0, so the
        # pages the kernel never copies weigh nothing
        btp = jnp.pad(block_table[:, :nj], ((0, 0), (0, nb * ppb - nj)))
        live = (jnp.arange(nb * ppb)[None, :]
                < lane_pages[:, None])[:, :, None, None]
        side = [jnp.where(live, pos_pool[btp][..., None], -1)]
        if quant:
            side += [jnp.where(live, kv_ops[i][btp], 0.0) for i in (1, 3)]
        side = [_block_columns(jnp.broadcast_to(x, (b, nb * ppb, tp, hkv)),
                               b, nb, cols, paired) for x in side]
        # column c holds a key of kv head _column_order(...)[c] % hkv:
        # each query head attends its own group's columns
        head_mask = jnp.asarray(
            _column_order(cols, paired)[None, :] % hkv
            == np.arange(h)[:, None] // rep, jnp.int32)

        def fetch(refs, lane, blk, slot, start):
            """Start (or wait for) the DMAs of one block: the lane's live
            pages in it, one copy per page and leaf (the fetch plan of
            :func:`_block_pages`)."""
            bt_ref, lp_ref, srcs, bufs, sems = refs

            def page(pg, carry):
                src = bt_ref[lane, blk * ppb + pg] if start else 0
                for x, buf, sem in zip(srcs, bufs, sems):
                    cp = pltpu.make_async_copy(x.at[src], buf.at[slot, pg],
                                               sem.at[slot])
                    if start:
                        cp.start()
                    else:
                        cp.wait()
                return carry
            jax.lax.fori_loop(
                0, _block_pages(lp_ref[lane], blk, ppb), page, 0)

        def kernel(bt_ref, pos_ref, lp_ref, q_ref, mask_ref, *refs):
            (*side_refs, k_hbm, v_hbm, o_ref, m_ref, l_ref, acc_ref,
             kbuf, vbuf, ksem, vsem, slot_ref) = refs
            i, blk = pl.program_id(0), pl.program_id(1)
            dma = (bt_ref, lp_ref, (k_hbm, v_hbm), (kbuf, vbuf),
                   (ksem, vsem))

            @pl.when((i == 0) & (blk == 0))
            def _():
                # pages past a lane's live count are never fetched: their
                # buffer slots keep an earlier block's (finite) keys or
                # these zeros, and their columns are masked
                kbuf[...] = jnp.zeros_like(kbuf)
                vbuf[...] = jnp.zeros_like(vbuf)
                slot_ref[0] = 0
                fetch(dma, 0, 0, 0, True)

            _init_accumulators(m_ref, l_ref, acc_ref)

            @pl.when(_block_pages(lp_ref[i], blk, ppb) > 0)
            def _():
                slot = slot_ref[0]
                nxt_i, nxt_blk = _next_block(i, blk, lp_ref[i], ppb)

                @pl.when(nxt_i < b)
                def _():
                    fetch(dma, nxt_i, nxt_blk, 1 - slot, True)

                slot_ref[0] = 1 - slot
                fetch(dma, i, blk, slot, False)

                qv = q_ref[0]                                # (H, D)
                kt = _block_values(kbuf[slot], quant, paired)
                kt = kt.astype(jnp.promote_types(kt.dtype, qv.dtype))
                s = jax.lax.dot_general(                     # (H, cols)
                    qv.astype(kt.dtype), kt, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                if quant:
                    pt_ref, kd_ref, vd_ref = side_refs
                    s = s * kd_ref[0]
                else:
                    (pt_ref,) = side_refs
                s = s * scale
                if softcap:
                    s = softcap * jnp.tanh(s / softcap)
                pt = pt_ref[0]                               # (1, cols)
                pb = pos_ref[i]
                valid = (mask_ref[...] != 0) & (pt >= 0) & (pt <= pb)
                if window:
                    valid &= pt > pb - window
                s = jnp.where(valid, s, NEG_INF)

                def v_tile(p):
                    if quant:
                        p = p * vd_ref[0]
                    vt = _block_values(vbuf[slot], quant, paired)
                    return jax.lax.dot_general(              # (H, Dv)
                        p, vt.astype(jnp.float32), (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)

                _online_update(s, valid, v_tile, m_ref, l_ref, acc_ref)

            _finish(o_ref, acc_ref, l_ref, nb)

        # a dead block's side data resolves to the lane's last live block,
        # which Pallas keeps resident instead of copying again
        def side_map(i, blk, bt, ps, lp):
            return i, 0, jnp.minimum(blk, (lp[i] - 1) // ppb)

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, nb),
            in_specs=[
                pl.BlockSpec((1, h, d), lambda i, j, bt, ps, lp: (i, 0, 0)),
                pl.BlockSpec((h, cols), lambda i, j, bt, ps, lp: (0, 0)),
                *[pl.BlockSpec((1, 1, cols), side_map) for _ in side],
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, h, dv),
                                   lambda i, j, bt, ps, lp: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((h, _LANES), jnp.float32),
                pltpu.VMEM((h, _LANES), jnp.float32),
                pltpu.VMEM((h, dv), jnp.float32),
                pltpu.VMEM((2, ppb, *k_rows.shape[1:]), k_rows.dtype),
                pltpu.VMEM((2, ppb, *v_rows.shape[1:]), v_rows.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        )
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, h, dv), jnp.float32),
            # each step prefetches the next live block, so the grid runs
            # in order
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interpret,
            name=_kernel_name("attn_decode", "ring" if window else "full",
                              quant or kv_ops[0].dtype),
        )(block_table[:, :nj], pos, lane_pages, q, head_mask, *side,
          k_rows, v_rows)

    args = (block_table, pos, lane_pages, q, *kv, pos_pool)
    if mesh is None:
        return shard_run(*args)
    PS = jax.sharding.PartitionSpec
    msize = mesh.shape.get("model", 1)
    if msize > 1 and hkv % msize == 0 and h % msize == 0:
        # embarrassingly parallel over head groups: each device attends
        # its own kv-head slice of the pools with its own q heads — no
        # collectives, and per-shard shapes keep the lane contract
        head4 = PS(None, None, "model", None)
        head3 = PS(None, None, "model")
        kv_in = (head4, head3, head4, head3) if quant else (head4, head4)
        in_specs = (PS(), PS(), PS(), PS(None, "model", None), *kv_in, PS())
        out_specs = PS(None, "model", None)
    else:
        # kv heads don't split evenly (GQA/MQA with few heads): run the
        # whole kernel replicated on every device — redundant compute,
        # but sharded pool operands are re-gathered and results stay
        # bitwise identical to the single-device call
        in_specs = tuple(PS() for _ in args)
        out_specs = PS()
    return jax.shard_map(shard_run, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(*args)


# ---------------------------------------------------------------------------
# MLA: absorbed latent attention over c_kv / k_rope page pools
# ---------------------------------------------------------------------------

def paged_mla_decode(q_eff: jax.Array, q_rope: jax.Array,
                     ckv_pool: jax.Array, krope_pool: jax.Array,
                     block_table: jax.Array, pos: jax.Array, *,
                     scale: float, active_pages: int | None = None,
                     lane_pages: jax.Array | None = None,
                     impl: str | None = None,
                     interpret: bool | None = None,
                     mesh=None) -> jax.Array:
    """Fused one-token paged MLA decode, absorbed form.

    q_eff: (B, H, R) query pre-multiplied by the absorbed ``kv_b`` key
    projection; q_rope: (B, H, Dr) decoupled-RoPE query; ckv_pool:
    (num_pages, P, R); krope_pool: (num_pages, P, Dr).  Latent pools carry
    no positions: entry ``j * P + o`` is valid iff its logical index is
    ``<= pos`` (matching :func:`repro.models.mla.mla_decode`).
    ``lane_pages`` bounds per-lane reads as in :func:`paged_attn_decode`
    (the positional mask already excludes the clamped revisits — their
    unclamped logical indices exceed ``pos``).  Returns the attended
    latents (B, H, R) f32 — the caller projects out with ``w_vb``.
    """
    return _mla_core(
        q_eff, q_rope, (ckv_pool, krope_pool), block_table, pos,
        _lane_bound(lane_pages, q_eff.shape[0],
                    _n_active(block_table, active_pages)),
        scale=scale,
        nj=_n_active(block_table, active_pages), impl=_resolve_impl(impl),
        interpret=(_interpret_default() if interpret is None else interpret),
        quant=None, mesh=mesh)


def paged_mla_decode_quant(q_eff: jax.Array, q_rope: jax.Array,
                           ckv_qs: jax.Array, ckv_d: jax.Array,
                           kr_qs: jax.Array, kr_d: jax.Array,
                           block_table: jax.Array, pos: jax.Array, *,
                           scale: float,
                           latent_mode: str = "q8_0",
                           rope_mode: str = "q8_0",
                           active_pages: int | None = None,
                           lane_pages: jax.Array | None = None,
                           impl: str | None = None,
                           interpret: bool | None = None,
                           mesh=None) -> jax.Array:
    """:func:`paged_mla_decode` over quantized latent/rope pools.

    ``ckv_qs``/``kr_qs``: int8 value pools (num_pages, P, R[dr] — halved
    when that leaf is q4_0, two nibbles per byte); ``ckv_d``/``kr_d``:
    per-(page, token) f32 scales (num_pages, P) — block = the latent/rope
    width.  ``latent_mode``/``rope_mode`` may differ: the "dq" per-layer
    policy keeps MLA latents (the dominant error path measured in the
    PR 5 budgets) at q8_0 while rope keys drop to q4_0.  Dequantisation
    happens inside the online-softmax loop; numerically exact w.r.t.
    attending the dequantised pools.
    """
    return _mla_core(
        q_eff, q_rope, (ckv_qs, ckv_d, kr_qs, kr_d), block_table, pos,
        _lane_bound(lane_pages, q_eff.shape[0],
                    _n_active(block_table, active_pages)),
        scale=scale, nj=_n_active(block_table, active_pages),
        impl=_resolve_impl(impl),
        interpret=(_interpret_default() if interpret is None else interpret),
        quant=(latent_mode, rope_mode), mesh=mesh)


def paged_mla_decode_q8(q_eff: jax.Array, q_rope: jax.Array,
                        ckv_qs: jax.Array, ckv_d: jax.Array,
                        kr_qs: jax.Array, kr_d: jax.Array,
                        block_table: jax.Array, pos: jax.Array, *,
                        scale: float, active_pages: int | None = None,
                        lane_pages: jax.Array | None = None,
                        impl: str | None = None,
                        interpret: bool | None = None,
                        mesh=None) -> jax.Array:
    """:func:`paged_mla_decode_quant` with both leaves pinned to q8_0
    (the original PR 5 entry point, kept for callers and parity suites
    that address the uniform-q8 layout by name)."""
    return paged_mla_decode_quant(
        q_eff, q_rope, ckv_qs, ckv_d, kr_qs, kr_d, block_table, pos,
        scale=scale, latent_mode="q8_0", rope_mode="q8_0",
        active_pages=active_pages, lane_pages=lane_pages, impl=impl,
        interpret=interpret, mesh=mesh)


def _xla_mla(q_eff, q_rope, cs, ks, pos, *, scale):
    """Bounded-gather XLA twin of the MLA kernel, over gathered latents."""
    s = (jnp.einsum("bhr,blr->bhl", q_eff.astype(jnp.float32), cs,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhd,bld->bhl", q_rope.astype(jnp.float32), ks,
                      preferred_element_type=jnp.float32)) * scale
    valid = jnp.arange(cs.shape[1])[None, :] <= pos[:, None]
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhl,blr->bhr", w, cs,
                      preferred_element_type=jnp.float32)


@partial(jax.jit, static_argnames=("scale", "nj", "impl", "interpret",
                                   "quant", "mesh"))
def _mla_core(q_eff, q_rope, kv, block_table, pos, lane_pages, *,
              scale: float, nj: int, impl: str, interpret: bool,
              quant: tuple | None, mesh=None) -> jax.Array:
    """Shared absorbed-MLA scaffold; ``kv`` is ``(ckv_pool, krope_pool)``
    (``quant=None``) or the quadruple ``(ckv_qs, ckv_d, kr_qs, kr_d)``
    with ``quant=(latent_mode, rope_mode)`` naming each leaf pair's
    storage mode (see :func:`_attn_core` for the tile-loader /
    lane-clamp pattern).  MLA
    validity is positional (unclamped ``kidx <= pos``), so lane-clamped
    trailing revisits are masked with no extra predicate.

    ``mesh`` (static): run the Pallas path under ``shard_map``, splitting
    the query-head axis over ``model`` when divisible (latent pools are
    per-token, not per-head, so every device reads them whole)."""
    b, h, r = q_eff.shape
    if impl == "xla":
        del lane_pages  # positional kidx <= pos mask already bounds lanes
        dr = q_rope.shape[-1]
        tp = kv[0].shape[1]
        btj = block_table[:, :nj]
        cs, ks = _gathered_kv(kv, btj, quant)
        return _xla_mla(q_eff, q_rope, cs.reshape(b, nj * tp, r),
                        ks.reshape(b, nj * tp, dr), pos, scale=scale)

    def shard_run(block_table, pos, lane_pages, q_eff, q_rope, *kv_ops):
        """Build + invoke the pallas_call; shapes derive from operands,
        which are *per-shard* inside shard_map."""
        b, h, r = q_eff.shape
        dr = q_rope.shape[-1]
        tp = kv_ops[0].shape[1]

        def kernel(bt_ref, pos_ref, lp_ref, qe_ref, qr_ref, *refs):
            del bt_ref, lp_ref
            *kv_refs, o_ref, m_ref, l_ref, acc_ref = refs
            _init_accumulators(m_ref, l_ref, acc_ref)
            if quant:
                cq_ref, cd_ref, kq_ref, kd_ref = kv_refs
                ckv = _dequant_rows(cq_ref[0], cd_ref[0], quant[0])
                krope = _dequant_rows(kq_ref[0], kd_ref[0], quant[1])
            else:
                ckv_ref, kr_ref = kv_refs
                ckv = ckv_ref[0].astype(jnp.float32)         # (P, R)
                krope = kr_ref[0].astype(jnp.float32)        # (P, Dr)
            s = (jnp.dot(qe_ref[0].astype(jnp.float32), ckv.T,
                         preferred_element_type=jnp.float32)
                 + jnp.dot(qr_ref[0].astype(jnp.float32), krope.T,
                           preferred_element_type=jnp.float32)) * scale
            valid = _key_index(tp) <= pos_ref[pl.program_id(0)]  # (1, P)
            s = jnp.where(valid, s, NEG_INF)
            _online_update(s, valid, lambda p: jnp.dot(
                p, ckv, preferred_element_type=jnp.float32),
                m_ref, l_ref, acc_ref)
            _finish(o_ref, acc_ref, l_ref, nj)

        pj = lambda i, j, bt, ps, lp: bt[i, jnp.minimum(j, lp[i] - 1)]  # noqa: E731,E501
        page3 = lambda i, j, bt, ps, lp: (pj(i, j, bt, ps, lp), 0, 0)  # noqa: E731,E501
        if quant:
            kv_ops = (kv_ops[0], _col_leaf(kv_ops[1]),
                      kv_ops[2], _col_leaf(kv_ops[3]))
        if quant:
            # packed trailing axes for q4_0 leaves — unpack is in-kernel
            kv_specs = [
                pl.BlockSpec((1, tp, kv_ops[0].shape[-1]), page3),
                pl.BlockSpec((1, tp, 1), page3),
                pl.BlockSpec((1, tp, kv_ops[2].shape[-1]), page3),
                pl.BlockSpec((1, tp, 1), page3),
            ]
        else:
            kv_specs = [
                pl.BlockSpec((1, tp, r), page3),
                pl.BlockSpec((1, tp, dr), page3),
            ]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, nj),
            in_specs=[
                pl.BlockSpec((1, h, r), lambda i, j, bt, ps, lp: (i, 0, 0)),
                pl.BlockSpec((1, h, dr), lambda i, j, bt, ps, lp: (i, 0, 0)),
                *kv_specs,
            ],
            out_specs=pl.BlockSpec((1, h, r),
                                   lambda i, j, bt, ps, lp: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((h, _LANES), jnp.float32),
                pltpu.VMEM((h, _LANES), jnp.float32),
                pltpu.VMEM((h, r), jnp.float32),
            ],
        )
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, h, r), jnp.float32),
            interpret=interpret,
            name=_kernel_name("mla_decode", quant or kv_ops[0].dtype),
        )(block_table, pos, lane_pages, q_eff, q_rope, *kv_ops)

    args = (block_table, pos, lane_pages, q_eff, q_rope, *kv)
    if mesh is None:
        return shard_run(*args)
    PS = jax.sharding.PartitionSpec
    msize = mesh.shape.get("model", 1)
    if msize > 1 and h % msize == 0:
        # query heads split across model; latent/rope pools are per-token
        # (no head axis), so each device reads them whole — no collectives
        headq = PS(None, "model", None)
        kv_in = tuple(PS() for _ in kv)
        in_specs = (PS(), PS(), PS(), headq, headq, *kv_in)
        out_specs = PS(None, "model", None)
    else:
        in_specs = tuple(PS() for _ in args)
        out_specs = PS()
    return jax.shard_map(shard_run, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(*args)


# ---------------------------------------------------------------------------
# quantized K/V page pools (Engine(kv_quant="q8_0" | "q4_0" | "dq"))
# ---------------------------------------------------------------------------

def quantize_kv_page_pool(pool: jax.Array) -> tuple[jax.Array, jax.Array]:
    """q8_0-style per-row quantization over the trailing axis.

    pool: (..., D) float -> (qs int8 same shape, d (...) f32) with
    ``x ~ qs * d``, ``d = max|x| / 127`` per row.  For K/V page pools
    (num_pages, P, Hkv, D) the block is ``head_dim`` (one scale per
    (page, token, head) row); for MLA latent pools (num_pages, P, R) the
    block is the latent width (one scale per token row) — exactly the
    layout the quantized cache leaves store (~4x less page traffic than
    f32 pools).  models/paged.py quantizes new rows with this same
    function on write, and tests/test_kv_quant.py pins it bitwise against
    the numpy oracle.
    """
    x = pool.astype(jnp.float32)
    d = jnp.max(jnp.abs(x), axis=-1) / 127.0
    safe = jnp.maximum(d, 1e-30)
    qs = jnp.clip(jnp.round(x / safe[..., None]), -127, 127).astype(jnp.int8)
    return qs, d


def pack_q4_rows(qs: jax.Array) -> jax.Array:
    """Pack int4-valued int8 rows two-per-byte along the trailing axis.

    qs: (..., D) int8 with every value in [-8, 7] (the q4_0 quantizer
    stays in [-7, 7]); D must be even.  Byte ``i`` holds element ``i``
    in its low nibble and element ``i + D/2`` in its high nibble — ggml's
    q4_0 layout with the whole row as the block — so
    :func:`unpack_q4_rows` restores the original element order with two
    arithmetic shifts and one concatenation, no interleave (a lane
    interleave is a shape cast the TPU compiler refuses).
    """
    width = qs.shape[-1]
    if width % 2:
        raise ValueError(f"q4_0 packing needs an even trailing dim; "
                         f"got {width}")
    half = width // 2
    lo = jnp.bitwise_and(qs[..., :half], 0x0F)
    hi = jnp.left_shift(qs[..., half:], 4)
    return jnp.bitwise_or(lo, hi).astype(jnp.int8)


def _q4_values(packed: jax.Array) -> jax.Array:
    """(..., D/2) packed bytes -> (..., D) int32 nibble values.

    32-bit arithmetic (the kernel tile loaders run this on the VPU, which
    has no 8-bit shifts): ``(b << 28) >> 28`` sign-extends the low
    nibble, ``(b << 24) >> 28`` the high one."""
    b = packed.astype(jnp.int32)
    lo = jnp.right_shift(jnp.left_shift(b, 28), 28)
    hi = jnp.right_shift(jnp.left_shift(b, 24), 28)
    return jnp.concatenate([lo, hi], axis=-1)


def unpack_q4_rows(packed: jax.Array) -> jax.Array:
    """Invert :func:`pack_q4_rows`: (..., D/2) int8 -> (..., D) int8."""
    return _q4_values(packed).astype(jnp.int8)


def quantize_kv_page_pool_q4(pool: jax.Array
                             ) -> tuple[jax.Array, jax.Array]:
    """q4_0-style per-row quantization: symmetric int4 in [-7, 7].

    Same row blocking as :func:`quantize_kv_page_pool` (``d = max|x|/7``
    per trailing-axis row) but the int values are nibble-packed two per
    byte (:func:`pack_q4_rows`), so the stored leaf's trailing axis is
    ``D // 2`` — ~7x less page traffic than f32 pools at ~16x the q8_0
    error ceiling (1/14 vs 1/254 of the row amplitude).
    """
    x = pool.astype(jnp.float32)
    d = jnp.max(jnp.abs(x), axis=-1) / 7.0
    safe = jnp.maximum(d, 1e-30)
    qs = jnp.clip(jnp.round(x / safe[..., None]), -7, 7).astype(jnp.int8)
    return pack_q4_rows(qs), d


def _check_mode(mode: str) -> str:
    if mode not in ("q8_0", "q4_0"):
        raise ValueError(f"unknown kv-quant storage mode {mode!r}")
    return mode


def paged_attn_decode_quant(q: jax.Array, k_qs: jax.Array, k_d: jax.Array,
                            v_qs: jax.Array, v_d: jax.Array,
                            pos_pool: jax.Array, block_table: jax.Array,
                            pos: jax.Array, *, mode: str = "q8_0",
                            window: int = 0,
                            softcap: float = 0.0,
                            scale: float | None = None,
                            active_pages: int | None = None,
                            lane_pages: jax.Array | None = None,
                            impl: str | None = None,
                            interpret: bool | None = None,
                            mesh=None) -> jax.Array:
    """:func:`paged_attn_decode` over quantized page pools.

    ``k_qs``/``v_qs``: int8 value pools (trailing axis halved under
    ``mode="q4_0"`` — two nibbles per byte), ``k_d``/``v_d``: their
    per-row scales (see :func:`quantize_kv_page_pool` /
    :func:`quantize_kv_page_pool_q4`).  Pages stream in packed;
    dequantisation happens inside the online-softmax loop (VPU), so the
    HBM traffic per page is ~1/4 (q8_0) / ~1/7 (q4_0) of the f32 pools'.
    Numerically exact w.r.t. attending the dequantised pools.
    """
    nj = _n_active(block_table, active_pages)
    return _attn_core(
        q, (k_qs, k_d, v_qs, v_d), pos_pool, block_table, pos,
        _lane_bound(lane_pages, q.shape[0], nj),
        window=window, softcap=softcap,
        scale=(q.shape[-1] ** -0.5 if scale is None else scale),
        nj=nj, ppb=_pages_per_block(k_qs.shape[1], nj),
        impl=_resolve_impl(impl),
        interpret=(_interpret_default() if interpret is None else interpret),
        quant=_check_mode(mode), mesh=mesh)


def paged_attn_decode_q8(q: jax.Array, k_qs: jax.Array, k_d: jax.Array,
                         v_qs: jax.Array, v_d: jax.Array,
                         pos_pool: jax.Array, block_table: jax.Array,
                         pos: jax.Array, *, window: int = 0,
                         softcap: float = 0.0, scale: float | None = None,
                         active_pages: int | None = None,
                         lane_pages: jax.Array | None = None,
                         impl: str | None = None,
                         interpret: bool | None = None,
                         mesh=None) -> jax.Array:
    """:func:`paged_attn_decode_quant` pinned to q8_0 (the original PR 5
    entry point, kept for callers that address the layout by name)."""
    return paged_attn_decode_quant(
        q, k_qs, k_d, v_qs, v_d, pos_pool, block_table, pos, mode="q8_0",
        window=window, softcap=softcap, scale=scale,
        active_pages=active_pages, lane_pages=lane_pages, impl=impl,
        interpret=interpret, mesh=mesh)


# ---------------------------------------------------------------------------
# fused chunked prefill over quantized pools (write-then-attend)
# ---------------------------------------------------------------------------

def paged_attn_prefill_quant(q: jax.Array, k_qs: jax.Array, k_d: jax.Array,
                             v_qs: jax.Array, v_d: jax.Array,
                             pos_pool: jax.Array, block_table: jax.Array,
                             qpos: jax.Array, *, mode: str = "q8_0",
                             window: int = 0, softcap: float = 0.0,
                             scale: float | None = None,
                             active_pages: int | None = None,
                             impl: str | None = None,
                             interpret: bool | None = None,
                             mesh=None) -> jax.Array:
    """Fused chunked-prefill GQA over quantized page pools.

    The caller has already quantized this chunk's K/V rows **once** and
    scattered them into the pools (write-then-attend, see
    models/attention.py); this kernel then attends every chunk query
    against the pools in place — no dense dequantised view is ever
    materialised, closing the prefill half of the packed-pages story.

    q: (B, C, H, D) chunk queries (RoPE applied, unscaled); qpos: (B, C)
    int32 absolute query positions, ``-1`` for padded rows (their outputs
    are all-masked zeros).  A key row is attendable for query (b, c) iff
    it is written (``pos >= 0``), causal (``pos <= qpos[b, c]``), inside
    the window when one applies, and its *logical* index is
    ``<= qpos[b, c]`` — full-table pools store position == logical index,
    so the last term masks stale rows beyond the lane's frontier left by
    a previous page occupant (the paged analogue of the gather path's
    ``pos < start`` frontier check).  Because the page enumeration is
    fixed by the block table — independent of how the prompt was split
    into chunks — the Pallas kernel's outputs are bitwise chunk-size
    invariant: pages past a query's horizon are fully masked, and
    fully-masked tiles are exact no-ops in the online softmax.  The XLA
    twin matches to float reassociation only (XLA blocks its einsums
    and softmax sums by the chunk shape).

    Returns (B, C, H, Dv) f32.  Ring (windowed-local) tables must keep
    the gather path: their stored positions are not logical indices.
    ``mesh``: as in :func:`paged_attn_decode_quant` (head-split
    ``shard_map`` when the kv heads divide the ``model`` axis).
    """
    nj = _n_active(block_table, active_pages)
    return _attn_prefill_core(
        q, (k_qs, k_d, v_qs, v_d), pos_pool, block_table,
        qpos.astype(jnp.int32),
        window=window, softcap=softcap,
        scale=(q.shape[-1] ** -0.5 if scale is None else scale),
        nj=nj, impl=_resolve_impl(impl),
        interpret=(_interpret_default() if interpret is None else interpret),
        quant=_check_mode(mode), mesh=mesh)


@partial(jax.jit, static_argnames=("window", "softcap", "scale", "nj",
                                   "impl", "interpret", "quant", "mesh"))
def _attn_prefill_core(q, kv, pos_pool, block_table, qpos, *,
                       window: int, softcap: float, scale: float, nj: int,
                       impl: str, interpret: bool,
                       quant: str, mesh=None) -> jax.Array:
    """Multi-query variant of :func:`_attn_core` for chunked prefill.

    Grid is the same ``(slot, logical_page)``; each step scores all C
    chunk queries against one page tile with a per-row (C, P) validity
    mask.  Rows are laid out ``(hkv, C, rep)`` so the score/probability
    contractions stay grouped by kv head: the wrapper arranges the
    queries, their per-row positions (a (rows, 1) column) and the output
    in that order outside the kernel, which then needs no shape cast
    beyond merging leading axes.  No lane clamp: every logical page in
    ``[0, nj)`` is either allocated to the lane or the NULL page (whose
    rows are unwritten, ``pos = -1``), and revisit-dedup does not apply
    because prefill reads each page exactly once.
    """
    b, c, h, d = q.shape
    tp, hkv = kv[0].shape[1], kv[0].shape[2]
    rep = h // hkv
    dv = kv[2].shape[-1] * (2 if quant == "q4_0" else 1)
    if impl == "xla":
        btj = block_table[:, :nj]
        ks, vs = _gathered_kv(kv, btj, quant)
        ks = ks.reshape(b, nj * tp, hkv, d)
        vs = vs.reshape(b, nj * tp, hkv, dv)
        ps = pos_pool[btj].reshape(b, nj * tp)
        kidx = jnp.arange(nj * tp)
        valid = ((ps[:, None, :] >= 0)
                 & (ps[:, None, :] <= qpos[:, :, None])
                 & (kidx[None, None, :] <= qpos[:, :, None]))
        if window:
            valid &= ps[:, None, :] > qpos[:, :, None] - window
        qg = (q.astype(jnp.float32) * scale).reshape(b, c, hkv, rep, d)
        s = jnp.einsum("bckrd,blkd->bckrl", qg, ks,
                       preferred_element_type=jnp.float32)
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(valid[:, :, None, None, :], s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        # NEG_INF is finite, so a fully-masked row (padded chunk query)
        # softmaxes to uniform, not zero — zero it explicitly to match
        # the kernel's all-masked-row output.  For rows with any valid
        # key this is a bitwise no-op: exp(NEG_INF - m) underflows to 0.
        w = jnp.where(valid[:, :, None, None, :], w, 0.0)
        o = jnp.einsum("bckrl,blkd->bckrd", w, vs,
                       preferred_element_type=jnp.float32)
        return o.reshape(b, c, h, dv)

    def shard_run(block_table, qpos, q, *rest):
        """Build + invoke the pallas_call from the (per-shard) operands."""
        *kv_ops, pos_pool = rest
        b, c, h, d = q.shape
        hkv = kv_ops[0].shape[2]
        rep = h // hkv
        # each grid step takes a block of rb query rows per kv head (VMEM
        # stays bounded for whole-prompt chunks); axis 0 runs over (slot,
        # row block) as in _mla_prefill_core
        rb = _row_block(c * rep, _MAX_ROWS // hkv)
        nr = c * rep // rb
        rows = hkv * rb

        def kernel(bt_ref, q_ref, qp_ref, kq_ref, kd_ref, vq_ref, vd_ref,
                   pp_ref, o_ref, m_ref, l_ref, acc_ref):
            del bt_ref
            _init_accumulators(m_ref, l_ref, acc_ref)
            kt = _dequant(kq_ref[0], kd_ref[0], quant)       # (P, Hkv, D)
            qv = q_ref[0].astype(jnp.float32) * scale        # (Hkv, rb, D)
            s = jax.lax.dot_general(                         # (Hkv, rb, P)
                qv, kt, (((2,), (2,)), ((0,), (1,))),
                preferred_element_type=jnp.float32).reshape(rows, tp)
            if softcap:
                s = softcap * jnp.tanh(s / softcap)
            pt = pp_ref[0]                                   # (1, P)
            qp = qp_ref[0].reshape(rows, 1)
            vr = (pt >= 0) & (pt <= qp) & (_key_index(tp) <= qp)  # (rows, P)
            if window:
                vr &= pt > qp - window
            s = jnp.where(vr, s, NEG_INF)

            def v_tile(p):
                o = jax.lax.dot_general(                     # (Hkv, rb, Dv)
                    p.reshape(hkv, rb, tp),
                    _dequant(vq_ref[0], vd_ref[0], quant),
                    (((2,), (0,)), ((0,), (1,))),
                    preferred_element_type=jnp.float32)
                return o.reshape(rows, dv)

            _online_update(s, vr, v_tile, m_ref, l_ref, acc_ref)
            _finish(o_ref, acc_ref, l_ref, nj)

        page4 = lambda i, j, bt: (bt[i // nr, j], 0, 0, 0)  # noqa: E731
        page3 = lambda i, j, bt: (bt[i // nr, j], 0, 0)     # noqa: E731
        lane4 = lambda i, j, bt: (i // nr, 0, i % nr, 0)    # noqa: E731
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * nr, nj),
            in_specs=[
                pl.BlockSpec((1, hkv, rb, d), lane4),
                pl.BlockSpec((1, hkv, rb, 1), lane4),
                pl.BlockSpec((1, tp, hkv, kv_ops[0].shape[-1]), page4),
                pl.BlockSpec((1, tp, hkv), page3),
                pl.BlockSpec((1, tp, hkv, kv_ops[2].shape[-1]), page4),
                pl.BlockSpec((1, tp, hkv), page3),
                pl.BlockSpec((1, 1, tp), page3),
            ],
            out_specs=pl.BlockSpec((1, hkv, rb, dv), lane4),
            scratch_shapes=[
                pltpu.VMEM((rows, _LANES), jnp.float32),
                pltpu.VMEM((rows, _LANES), jnp.float32),
                pltpu.VMEM((rows, dv), jnp.float32),
            ],
        )
        # rows in (hkv, C, rep) order: queries, their positions, and back
        qg = q.reshape(b, c, hkv, rep, d).transpose(0, 2, 1, 3, 4)
        qrow = jnp.broadcast_to(qpos[:, None, :, None], (b, hkv, c, rep))
        o = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, hkv, c * rep, dv),
                                           jnp.float32),
            interpret=interpret,
            name=_kernel_name("attn_prefill", "ring" if window else "full",
                              quant),
        )(block_table, qg.reshape(b, hkv, c * rep, d),
          qrow.reshape(b, hkv, c * rep, 1), *kv_ops, _row_leaf(pos_pool))
        return o.reshape(b, hkv, c, rep, dv).transpose(0, 2, 1, 3, 4
                                                       ).reshape(b, c, h, dv)

    args = (block_table, qpos, q, *kv, pos_pool)
    if mesh is None:
        return shard_run(*args)
    PS = jax.sharding.PartitionSpec
    msize = mesh.shape.get("model", 1)
    if msize > 1 and hkv % msize == 0 and h % msize == 0:
        # head groups are independent, as in _attn_core
        head4 = PS(None, None, "model", None)
        head3 = PS(None, None, "model")
        in_specs = (PS(), PS(), head4, head4, head3, head4, head3, PS())
        out_specs = head4
    else:
        in_specs = tuple(PS() for _ in args)
        out_specs = PS()
    return jax.shard_map(shard_run, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(*args)


def paged_mla_prefill_quant(q_eff: jax.Array, q_rope: jax.Array,
                            ckv_qs: jax.Array, ckv_d: jax.Array,
                            kr_qs: jax.Array, kr_d: jax.Array,
                            block_table: jax.Array, qpos: jax.Array, *,
                            scale: float,
                            latent_mode: str = "q8_0",
                            rope_mode: str = "q8_0",
                            active_pages: int | None = None,
                            impl: str | None = None,
                            interpret: bool | None = None,
                            mesh=None) -> jax.Array:
    """Fused chunked-prefill absorbed MLA over quantized latent pools.

    Write-then-attend like :func:`paged_attn_prefill_quant`, in absorbed
    form: q_eff (B, C, H, R) is the chunk's nope query pre-multiplied by
    the absorbed ``kv_b`` key projection, and the returned (B, C, H, R)
    f32 latents are projected out with ``w_vb`` by the caller — no
    per-head K/V is materialised, matching the decode path's math rather
    than the naive gather prefill's.  Latent pools store no positions:
    validity is purely ``logical_idx <= qpos[b, c]`` (padded rows carry
    ``qpos = -1`` and come back zero).  ``mesh``: as in
    :func:`paged_mla_decode_quant` (query heads split on ``model``).
    """
    nj = _n_active(block_table, active_pages)
    return _mla_prefill_core(
        q_eff, q_rope, (ckv_qs, ckv_d, kr_qs, kr_d), block_table,
        qpos.astype(jnp.int32),
        scale=scale, nj=nj, impl=_resolve_impl(impl),
        interpret=(_interpret_default() if interpret is None else interpret),
        quant=(_check_mode(latent_mode), _check_mode(rope_mode)), mesh=mesh)


@partial(jax.jit, static_argnames=("scale", "nj", "impl", "interpret",
                                   "quant", "mesh"))
def _mla_prefill_core(q_eff, q_rope, kv, block_table, qpos, *,
                      scale: float, nj: int, impl: str, interpret: bool,
                      quant: tuple, mesh=None) -> jax.Array:
    """Multi-query variant of :func:`_mla_core` for chunked prefill;
    rows are ``(C, h)``-ordered, validity is the per-row positional mask
    ``logical_idx <= qpos``."""
    b, c, h, r = q_eff.shape
    dr = q_rope.shape[-1]
    tp = kv[0].shape[1]
    if impl == "xla":
        btj = block_table[:, :nj]
        cs, ks = _gathered_kv(kv, btj, quant)
        cs = cs.reshape(b, nj * tp, r)
        ks = ks.reshape(b, nj * tp, dr)
        kidx = jnp.arange(nj * tp)
        valid = kidx[None, None, :] <= qpos[:, :, None]
        s = (jnp.einsum("bchr,blr->bchl", q_eff.astype(jnp.float32), cs,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bchd,bld->bchl", q_rope.astype(jnp.float32), ks,
                          preferred_element_type=jnp.float32)) * scale
        s = jnp.where(valid[:, :, None, :], s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        # zero fully-masked (padded) rows — see _attn_prefill_core
        w = jnp.where(valid[:, :, None, :], w, 0.0)
        return jnp.einsum("bchl,blr->bchr", w, cs,
                          preferred_element_type=jnp.float32)

    def shard_run(block_table, qpos, q_eff, q_rope, *kv_ops):
        """Build + invoke the pallas_call from the (per-shard) operands."""
        b, c, h, r = q_eff.shape
        rows = c * h
        rb = _row_block(rows, _MAX_ROWS)
        nr = rows // rb

        def kernel(bt_ref, qe_ref, qr_ref, qp_ref, cq_ref, cd_ref, kq_ref,
                   kd_ref, o_ref, m_ref, l_ref, acc_ref):
            del bt_ref
            _init_accumulators(m_ref, l_ref, acc_ref)
            ckv = _dequant_rows(cq_ref[0], cd_ref[0], quant[0])    # (P, R)
            krope = _dequant_rows(kq_ref[0], kd_ref[0], quant[1])  # (P, Dr)
            qe = qe_ref[0].astype(jnp.float32)               # (rb, R)
            qr = qr_ref[0].astype(jnp.float32)               # (rb, Dr)
            s = (jnp.dot(qe, ckv.T, preferred_element_type=jnp.float32)
                 + jnp.dot(qr, krope.T,
                           preferred_element_type=jnp.float32)) * scale
            vr = _key_index(tp) <= qp_ref[0]                 # (rb, P)
            s = jnp.where(vr, s, NEG_INF)
            _online_update(s, vr, lambda p: jnp.dot(
                p, ckv, preferred_element_type=jnp.float32),
                m_ref, l_ref, acc_ref)
            _finish(o_ref, acc_ref, l_ref, nj)

        # grid axis 0 runs over (slot, row block): each step holds rb
        # query rows, so VMEM stays bounded at wide head counts (128 heads
        # x C=32 rows would not fit whole)
        page3 = lambda i, j, bt: (bt[i // nr, j], 0, 0)  # noqa: E731
        lane3 = lambda i, j, bt: (i // nr, i % nr, 0)    # noqa: E731
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * nr, nj),
            in_specs=[
                pl.BlockSpec((1, rb, r), lane3),
                pl.BlockSpec((1, rb, dr), lane3),
                pl.BlockSpec((1, rb, 1), lane3),
                pl.BlockSpec((1, tp, kv_ops[0].shape[-1]), page3),
                pl.BlockSpec((1, tp, 1), page3),
                pl.BlockSpec((1, tp, kv_ops[2].shape[-1]), page3),
                pl.BlockSpec((1, tp, 1), page3),
            ],
            out_specs=pl.BlockSpec((1, rb, r), lane3),
            scratch_shapes=[
                pltpu.VMEM((rb, _LANES), jnp.float32),
                pltpu.VMEM((rb, _LANES), jnp.float32),
                pltpu.VMEM((rb, r), jnp.float32),
            ],
        )
        # rows in (C, H) order; each row carries its query's position
        qrow = jnp.broadcast_to(qpos[:, :, None], (b, c, h))
        o = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, rows, r), jnp.float32),
            interpret=interpret,
            name=_kernel_name("mla_prefill", quant),
        )(block_table, q_eff.reshape(b, rows, r), q_rope.reshape(b, rows, dr),
          qrow.reshape(b, rows, 1), kv_ops[0], _col_leaf(kv_ops[1]),
          kv_ops[2], _col_leaf(kv_ops[3]))
        return o.reshape(b, c, h, r)

    args = (block_table, qpos, q_eff, q_rope, *kv)
    if mesh is None:
        return shard_run(*args)
    PS = jax.sharding.PartitionSpec
    msize = mesh.shape.get("model", 1)
    if msize > 1 and h % msize == 0:
        # query heads split; the per-token latent pools are read whole
        headq = PS(None, None, "model", None)
        in_specs = (PS(), PS(), headq, headq, *(PS() for _ in kv))
        out_specs = headq
    else:
        in_specs = tuple(PS() for _ in args)
        out_specs = PS()
    return jax.shard_map(shard_run, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(*args)
