"""Attention blocks: GQA/MHA with RoPE, sliding windows, softcaps.

Prefill/train uses a flash-style *chunked* attention (online softmax over KV
chunks via ``jax.lax.scan``) so the 32k-token shapes never materialise an
(L x L) score matrix — this keeps the dry-run memory term honest and is one
of the beyond-paper optimizations recorded in EXPERIMENTS.md.

Decode attends one query position against a cache.  Local-attention layers
use a ring-buffer cache of ``window`` entries with absolute-position RoPE
(keys rotated at write time), so a 500k-token stream costs O(window) memory.
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..kernels import paged_attn
from . import paged
from .common import apply_rope, gather_heads, linear, rms_norm, softcap

NEG_INF = -2.0e38

# Paged decode kernel selection: "fused" (Pallas flash-decode over pages,
# the fast path) or "gather" (materialise the exact dense view first — the
# reference implementation the parity suite checks the kernel against).
PAGED_KERNEL_ENV = "REPRO_PAGED_KERNEL"


def default_paged_kernel() -> str:
    return os.environ.get(PAGED_KERNEL_ENV, "fused")

# PERF B1 (EXPERIMENTS.md §Perf): grouped-query attention without
# materialising jnp.repeat(kv, rep) — the repeat forces the SPMD partitioner
# to reshard sequence-sharded caches ("involuntary full rematerialization").
# The grouped einsum keeps KV in its (kv_heads,) layout end to end.
GQA_EINSUM = os.environ.get("REPRO_GQA_EINSUM", "0") == "1"


def _chunk_attn(q, k, v, mask_fn, attn_cap: float, chunk: int = 1024):
    """Online-softmax attention.

    q: (B, Tq, H, D); k/v: (B, Tk, Hkv, D); mask_fn(qi, ki) -> bool (Tq_c, Tk_c)
    given absolute query/key index arrays.  ``mask_fn`` may also return a
    per-row mask (B, Tq_c, Tk_c) — used by the chunked-prefill path, where
    every batch row sits at a different absolute position.  Returns
    (B, Tq, H, D).
    """
    b, tq, h, d = q.shape
    tk, hkv = k.shape[1], k.shape[2]
    dv = v.shape[-1]                      # may differ from d (MLA)
    rep = h // hkv
    scale = d ** -0.5
    chunk = max(16, min(chunk, tk))
    nk = -(-tk // chunk)
    pad_k = nk * chunk - tk
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    kc = k.reshape(b, nk, chunk, hkv, d)
    vc = v.reshape(b, nk, chunk, hkv, dv)

    def body(carry, inputs):
        m, l, acc = carry
        ki, kci, vci = inputs                        # index, (B,c,Hkv,D) x2
        kq = jnp.repeat(kci, rep, axis=2)
        vq = jnp.repeat(vci, rep, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kq,
                       preferred_element_type=jnp.float32) * scale
        if attn_cap:
            s = softcap(s, attn_cap)
        qi = jnp.arange(tq)
        kidx = ki * chunk + jnp.arange(chunk)
        valid = mask_fn(qi[:, None], kidx[None, :]) & (kidx < tk)[None, :]
        valid = valid[:, None] if valid.ndim == 3 else valid[None, None]
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(vq.dtype), vq,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, tq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, tq), jnp.float32)
    a0 = jnp.zeros((b, h, tq, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0),
        (jnp.arange(nk), jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.einsum("bhqd->bqhd", out)


def causal_mask_fn(window: int = 0):
    def fn(qi, ki):
        ok = ki <= qi
        if window:
            ok = ok & (ki > qi - window)
        return ok
    return fn


def full_mask_fn(valid_len=None):
    def fn(qi, ki):
        ok = jnp.ones(jnp.broadcast_shapes(qi.shape, ki.shape), bool)
        if valid_len is not None:
            ok = ok & (ki < valid_len)
        return ok
    return fn


# ---------------------------------------------------------------------------
# GQA block
# ---------------------------------------------------------------------------

def _qkv(p, cfg: ModelConfig, x, positions):
    b, t, _ = x.shape
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = linear(p["q_proj"], x, p.get("q_bias")).reshape(b, t, nh, hd)
    k = linear(p["k_proj"], x, p.get("k_bias")).reshape(b, t, nkv, hd)
    v = linear(p["v_proj"], x, p.get("v_bias")).reshape(b, t, nkv, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_forward(p: dict, cfg: ModelConfig, x: jax.Array, *,
                 local: bool, positions=None, kv_override=None,
                 causal: bool = True) -> jax.Array:
    """Full-sequence attention (train / prefill).  x: (B, T, D)."""
    b, t, _ = x.shape
    h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
    if positions is None:
        positions = jnp.arange(t)[None, :]
    if kv_override is None:
        q, k, v = _qkv(p, cfg, h, positions)
    else:  # cross attention: kv from encoder output
        nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        q = linear(p["q_proj"], h).reshape(b, t, nh, hd)
        k, v = kv_override
    window = cfg.window if local else 0
    mask = causal_mask_fn(window) if causal else full_mask_fn()
    o = _chunk_attn(q, k, v, mask, cfg.attn_softcap)
    o = o.reshape(b, t, cfg.n_heads * cfg.head_dim).astype(x.dtype)
    return linear(p["o_proj"], o)


# ---------------------------------------------------------------------------
# decode with cache
# ---------------------------------------------------------------------------

def init_attn_cache(cfg: ModelConfig, batch: int, max_len: int, local: bool,
                    dtype=jnp.bfloat16) -> dict:
    length = min(max_len, cfg.window) if (local and cfg.window) else max_len
    nkv, hd = cfg.n_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, length, nkv, hd), dtype),
        "v": jnp.zeros((batch, length, nkv, hd), dtype),
        "pos": jnp.full((batch, length), -1, jnp.int32),
    }


def attn_cache_specs(cfg: ModelConfig, batch: int, max_len: int, local: bool,
                     dtype=jnp.bfloat16) -> dict:
    length = min(max_len, cfg.window) if (local and cfg.window) else max_len
    nkv, hd = cfg.n_kv_heads, cfg.head_dim
    return {
        "k": jax.ShapeDtypeStruct((batch, length, nkv, hd), dtype),
        "v": jax.ShapeDtypeStruct((batch, length, nkv, hd), dtype),
        "pos": jax.ShapeDtypeStruct((batch, length), jnp.int32),
    }


def attn_prefill(p: dict, cfg: ModelConfig, x: jax.Array, max_len: int,
                 *, local: bool) -> tuple[jax.Array, dict]:
    """Full-sequence forward that also builds the decode cache.

    x: (B, T, D).  The cache covers positions [0, T); ring-buffered to
    ``window`` entries for local layers.
    """
    b, t, _ = x.shape
    h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
    positions = jnp.arange(t)[None, :]
    q, k, v = _qkv(p, cfg, h, positions)
    window = cfg.window if local else 0
    o = _chunk_attn(q, k, v, causal_mask_fn(window), cfg.attn_softcap)
    o = o.reshape(b, t, cfg.n_heads * cfg.head_dim).astype(x.dtype)
    out = linear(p["o_proj"], o)

    cache = init_attn_cache(cfg, b, max_len, local, dtype=k.dtype)
    length = cache["k"].shape[1]
    if length >= t:
        ck = cache["k"].at[:, :t].set(k)
        cv = cache["v"].at[:, :t].set(v)
        cpos = cache["pos"].at[:, :t].set(positions.astype(jnp.int32))
    else:  # ring buffer: keep the last ``length`` positions
        tail = slice(t - length, t)
        pos_tail = jnp.arange(t - length, t, dtype=jnp.int32)
        slots = pos_tail % length
        ck = cache["k"].at[:, slots].set(k[:, tail])
        cv = cache["v"].at[:, slots].set(v[:, tail])
        cpos = cache["pos"].at[:, slots].set(
            jnp.broadcast_to(pos_tail, (b, length)))
    return out, {"k": ck, "v": cv, "pos": cpos}


def cache_len(cfg: ModelConfig, max_len: int, local: bool) -> int:
    """Dense cache length for one attention layer (ring-bounded if local)."""
    return min(max_len, cfg.window) if (local and cfg.window) else max_len


def _kv_mode(kv_quant) -> str | None:
    """Normalize a per-layer cache-quant spec to this family's storage
    mode: GQA K/V leaves share one mode (``LayerQuant.kv``); ``None``
    keeps f32/model-dtype pools.  Only concrete modes are accepted here —
    the engine-level "dq" policy string is resolved per layer upstream
    (``paged.resolve_layer_quant`` in transformer.py)."""
    return paged.as_layer_quant(kv_quant).kv if kv_quant else None


def init_paged_attn_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                          dtype=jnp.bfloat16, kv_quant=None) -> dict:
    """Paged K/V/pos pools shared by every slot (see models/paged.py).

    ``kv_quant`` stores K/V as int8 pools plus per-(token, head) f32
    scale pools — ~4x ("q8_0") / ~7x ("q4_0", nibble-packed: the stored
    trailing axis is ``head_dim // 2``) less cache memory and decode page
    traffic; the ``pos`` pool is shared by every layout.
    """
    nkv, hd = cfg.n_kv_heads, cfg.head_dim
    pos = jnp.full((num_pages, page_size), -1, jnp.int32)
    mode = _kv_mode(kv_quant)
    if mode:
        hd_s = paged.q4_packed_dim(hd, "head") if mode == "q4_0" else hd
        return {
            "k_qs": jnp.zeros((num_pages, page_size, nkv, hd_s), jnp.int8),
            "k_d": jnp.zeros((num_pages, page_size, nkv), jnp.float32),
            "v_qs": jnp.zeros((num_pages, page_size, nkv, hd_s), jnp.int8),
            "v_d": jnp.zeros((num_pages, page_size, nkv), jnp.float32),
            "pos": pos,
        }
    return {
        "k": jnp.zeros((num_pages, page_size, nkv, hd), dtype),
        "v": jnp.zeros((num_pages, page_size, nkv, hd), dtype),
        "pos": pos,
    }


def paged_attn_cache_specs(cfg: ModelConfig, num_pages: int, page_size: int,
                           dtype=jnp.bfloat16, kv_quant=None) -> dict:
    nkv, hd = cfg.n_kv_heads, cfg.head_dim
    pos = jax.ShapeDtypeStruct((num_pages, page_size), jnp.int32)
    mode = _kv_mode(kv_quant)
    if mode:
        hd_s = paged.q4_packed_dim(hd, "head") if mode == "q4_0" else hd
        return {
            "k_qs": jax.ShapeDtypeStruct((num_pages, page_size, nkv, hd_s),
                                         jnp.int8),
            "k_d": jax.ShapeDtypeStruct((num_pages, page_size, nkv),
                                        jnp.float32),
            "v_qs": jax.ShapeDtypeStruct((num_pages, page_size, nkv, hd_s),
                                         jnp.int8),
            "v_d": jax.ShapeDtypeStruct((num_pages, page_size, nkv),
                                        jnp.float32),
            "pos": pos,
        }
    return {
        "k": jax.ShapeDtypeStruct((num_pages, page_size, nkv, hd), dtype),
        "v": jax.ShapeDtypeStruct((num_pages, page_size, nkv, hd), dtype),
        "pos": pos,
    }


def attn_decode_paged(p: dict, cfg: ModelConfig, x: jax.Array, cache: dict,
                      pos: jax.Array, block_table: jax.Array, *, local: bool,
                      max_len: int, live: jax.Array | None = None,
                      kernel: str | None = None,
                      active_pages: int | None = None,
                      lane_pages: jax.Array | None = None,
                      kv_quant: str | None = None,
                      mesh=None,
                      ) -> tuple[jax.Array, dict]:
    """One-token decode against a paged cache.

    ``kernel`` selects the implementation (default: ``REPRO_PAGED_KERNEL``
    env, else "fused"):

      * ``"fused"`` — scatter the new K/V/pos row into its page, then run
        the flash-decode Pallas kernel that reads the pages **in place**
        through the block table (no dense view; decode bandwidth scales
        with live pages — see kernels/paged_attn.py).  ``active_pages``
        optionally bounds the page loop to the batch's live horizon and
        ``lane_pages`` (B,) int32 further bounds each lane to its own
        live page count (gather ignores both — it is the full-table
        bitwise reference).
      * ``"gather"`` — reference implementation: gather the exact dense
        view, run the unchanged dense :func:`attn_decode` on it
        (bitwise-identical logits to the contiguous layout), scatter the
        newly written row back.

    ``kv_quant`` (a concrete mode or a ``paged.LayerQuant``) expects the
    quantized pool layout of :func:`init_paged_attn_cache`: the new K/V
    row is quantized *before*
    the write, so both kernels attend the same round-tripped values — the
    fused path dequantizes page tiles in the kernel (unpacking q4_0
    nibbles after the DMA), the gather reference
    dequantizes the gathered dense view.
    """
    kernel = kernel or default_paged_kernel()
    if kernel not in ("fused", "gather"):
        raise ValueError(f"unknown paged decode kernel {kernel!r}")
    kv_quant = _kv_mode(kv_quant)
    length = cache_len(cfg, max_len, local)
    b = x.shape[0]
    if kernel == "gather" and not kv_quant:
        dense = {k: gather_heads(
                     paged.gather_pages(cache[k], block_table, length), mesh)
                 for k in ("k", "v", "pos")}
        delta, dnew = attn_decode(p, cfg, x, dense, pos, local=local,
                                  live=live)
        bidx = jnp.arange(b)
        slot = (pos % length).astype(jnp.int32)
        new = {key: paged.scatter_token(cache[key], block_table, slot,
                                        dnew[key][bidx, slot], ok=live)
               for key in ("k", "v", "pos")}
        return delta, new

    h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
    q, k, v = _qkv(p, cfg, h, pos[:, None])
    slot = (pos % length).astype(jnp.int32)
    if kv_quant:
        kq, kd = paged.scatter_token_quant(cache["k_qs"], cache["k_d"],
                                           block_table, slot, k[:, 0],
                                           ok=live, mode=kv_quant)
        vq, vd = paged.scatter_token_quant(cache["v_qs"], cache["v_d"],
                                           block_table, slot, v[:, 0],
                                           ok=live, mode=kv_quant)
        new = {
            "k_qs": kq, "k_d": kd, "v_qs": vq, "v_d": vd,
            "pos": paged.scatter_token(cache["pos"], block_table, slot,
                                       pos.astype(jnp.int32), ok=live),
        }
        if kernel == "gather":
            # dequantizing gather reference: attend the dense view of the
            # *updated* pools so the round-tripped new row matches fused
            ck = paged.gather_pages_quant(kq, kd, block_table, length,
                                          kv_quant)
            cv = paged.gather_pages_quant(vq, vd, block_table, length,
                                          kv_quant)
            cpos = paged.gather_pages(new["pos"], block_table, length)
            o = _attend_cache(cfg, q, ck, cv, cpos, pos,
                              local=local).astype(x.dtype)
            return linear(p["o_proj"], gather_heads(o, mesh)), new
        o = paged_attn.paged_attn_decode_quant(
            q[:, 0], kq, kd, vq, vd, new["pos"], block_table, pos,
            mode=kv_quant,
            window=(cfg.window if local else 0), softcap=cfg.attn_softcap,
            scale=cfg.head_dim ** -0.5, active_pages=active_pages,
            lane_pages=lane_pages, mesh=mesh)
        o = o.reshape(b, 1, cfg.n_heads * cfg.head_dim).astype(x.dtype)
        return linear(p["o_proj"], gather_heads(o, mesh)), new

    new = {
        "k": paged.scatter_token(cache["k"], block_table, slot, k[:, 0],
                                 ok=live),
        "v": paged.scatter_token(cache["v"], block_table, slot, v[:, 0],
                                 ok=live),
        "pos": paged.scatter_token(cache["pos"], block_table, slot,
                                   pos.astype(jnp.int32), ok=live),
    }
    o = paged_attn.paged_attn_decode(
        q[:, 0], new["k"], new["v"], new["pos"], block_table, pos,
        window=(cfg.window if local else 0), softcap=cfg.attn_softcap,
        scale=cfg.head_dim ** -0.5, active_pages=active_pages,
        lane_pages=lane_pages, mesh=mesh)
    o = o.reshape(b, 1, cfg.n_heads * cfg.head_dim).astype(x.dtype)
    return linear(p["o_proj"], gather_heads(o, mesh)), new


def chunk_key_positions(old_pos: jax.Array, positions: jax.Array,
                        valid_tok: jax.Array) -> jax.Array:
    """Key positions over [old cache view | chunk]: cache entries carry
    their stored/logical position, chunk tokens theirs (-1 when padded)."""
    return jnp.concatenate(
        [old_pos, jnp.where(valid_tok, positions, -1).astype(jnp.int32)],
        axis=1)


def chunk_mask_fn(key_pos: jax.Array, n_old: int, positions: jax.Array,
                  start: jax.Array, window: int):
    """Per-row validity for chunked prefill over [old cache | chunk] keys.

    A key is attendable iff it is written (pos >= 0), causal (pos <= query
    pos), inside the sliding window when one applies, and — for cache-side
    entries — strictly below this request's write frontier (``pos <
    start``), which also masks stale entries left by a previous occupant
    of the slot or page.  Shared by the GQA and MLA chunk paths so the
    frontier semantics cannot drift apart.
    """
    total = key_pos.shape[1]
    from_old = jnp.arange(total) < n_old

    def mask_fn(qi, ki):
        kj = jnp.clip(ki[0], 0, total - 1)                         # (kc,)
        kp = key_pos[:, kj]                                        # (B, kc)
        qp = positions[:, :, None]                                 # (B, C, 1)
        ok = (kp[:, None, :] >= 0) & (kp[:, None, :] <= qp)
        ok &= jnp.where(from_old[kj][None, None, :],
                        kp[:, None, :] < start[:, None, None], True)
        if window:
            ok &= kp[:, None, :] > qp - window
        return ok

    return mask_fn


def attn_prefill_chunk(p: dict, cfg: ModelConfig, x: jax.Array, cache: dict,
                       positions: jax.Array, start: jax.Array,
                       chunk_len: jax.Array, *, local: bool, max_len: int,
                       block_table: jax.Array | None = None,
                       kv_quant=None, kernel: str | None = None,
                       active_pages: int | None = None, mesh=None,
                       ) -> tuple[jax.Array, dict]:
    """One prefill chunk against an existing (pooled) cache.

    x: (B, C, D) right-padded per row; positions: (B, C) absolute;
    start: (B,) first position of the chunk; chunk_len: (B,) valid tokens
    (0 = inactive row: no writes, output ignored).  Queries attend to the
    cache contents written by *earlier* chunks of the same request (entries
    with ``cpos < start``, which also masks stale entries left by a
    previous occupant of the slot) plus the causal prefix of the chunk
    itself.  Works on a dense pooled cache, or a paged one when
    ``block_table`` is given; with ``kv_quant`` the paged pools are
    quantized and this chunk's K/V are quantized once up front, so the
    chunk's own keys are attended through the same round-tripped values
    every later read sees, whatever the chunk size.

    ``kernel="fused"`` on a quantized full-horizon (non-ring) layer runs
    the *write-then-attend* path: the quantized rows are scattered into
    their pages first, then every chunk query attends the pools in place
    (:func:`repro.kernels.paged_attn.paged_attn_prefill_quant`) — packed
    pages stay packed, no dense dequantised view is ever materialised,
    and the page enumeration order does not depend on the chunk split.
    Ring layers and ``kernel="gather"`` keep the dequantizing-gather
    reference path.  ``mesh``: the serving mesh, forwarded to the fused
    kernel (runs it under ``shard_map``).
    """
    kv_quant = _kv_mode(kv_quant)
    kernel = kernel or default_paged_kernel()
    b, c, _ = x.shape
    length = cache_len(cfg, max_len, local)
    h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
    q, k, v = _qkv(p, cfg, h, positions)

    if (kv_quant and kernel == "fused" and not (local and cfg.window)):
        # write-then-attend: quantize once, scatter, then attend the
        # pages in place — full tables only (stored pos == logical index
        # is what lets the kernel mask stale rows beyond the frontier)
        valid_tok = jnp.arange(c)[None, :] < chunk_len[:, None]    # (B, C)
        idx = (positions % length).astype(jnp.int32)
        ok = paged.chunk_write_plan(idx, valid_tok, length)
        k_qs, k_d = paged.quantize_rows(k, kv_quant)
        v_qs, v_d = paged.quantize_rows(v, kv_quant)
        new = {
            "k_qs": paged.scatter_chunk(cache["k_qs"], block_table, idx,
                                        k_qs, ok),
            "k_d": paged.scatter_chunk(cache["k_d"], block_table, idx,
                                       k_d, ok),
            "v_qs": paged.scatter_chunk(cache["v_qs"], block_table, idx,
                                        v_qs, ok),
            "v_d": paged.scatter_chunk(cache["v_d"], block_table, idx,
                                       v_d, ok),
            "pos": paged.scatter_chunk(cache["pos"], block_table, idx,
                                       positions.astype(jnp.int32), ok),
        }
        qpos = jnp.where(valid_tok, positions, -1).astype(jnp.int32)
        o = paged_attn.paged_attn_prefill_quant(
            q, new["k_qs"], new["k_d"], new["v_qs"], new["v_d"],
            new["pos"], block_table, qpos, mode=kv_quant, window=0,
            softcap=cfg.attn_softcap, scale=cfg.head_dim ** -0.5,
            active_pages=active_pages, mesh=mesh)
        o = o.reshape(b, c, cfg.n_heads * cfg.head_dim).astype(x.dtype)
        return linear(p["o_proj"], gather_heads(o, mesh)), new

    k_qs = k_d = v_qs = v_d = None
    if kv_quant:
        assert block_table is not None, "kv_quant requires paged caches"
        ck = paged.gather_pages_quant(cache["k_qs"], cache["k_d"],
                                      block_table, length, kv_quant)
        cv = paged.gather_pages_quant(cache["v_qs"], cache["v_d"],
                                      block_table, length, kv_quant)
        cpos = paged.gather_pages(cache["pos"], block_table, length)
        # quantize the chunk's K/V once, up front: in-chunk attention uses
        # the round-tripped view and the same qs/d are scattered below, so
        # in-chunk and cross-chunk reads are identical
        k_qs, k_d, k_att = paged.roundtrip_quant(k, kv_quant)
        v_qs, v_d, v_att = paged.roundtrip_quant(v, kv_quant)
    elif block_table is not None:
        ck = paged.gather_pages(cache["k"], block_table, length)
        cv = paged.gather_pages(cache["v"], block_table, length)
        cpos = paged.gather_pages(cache["pos"], block_table, length)
        k_att, v_att = k, v
    else:
        ck, cv, cpos = cache["k"], cache["v"], cache["pos"]
        k_att, v_att = k, v

    # attend over [old cache view | chunk] so in-chunk ring writes can never
    # evict entries an earlier in-chunk query still needs
    valid_tok = jnp.arange(c)[None, :] < chunk_len[:, None]        # (B, C)
    key_pos = chunk_key_positions(cpos, positions, valid_tok)
    kk = jnp.concatenate([ck, k_att.astype(ck.dtype)], axis=1)
    vv = jnp.concatenate([cv, v_att.astype(cv.dtype)], axis=1)
    window = cfg.window if local else 0
    mask_fn = chunk_mask_fn(key_pos, length, positions, start, window)

    o = _chunk_attn(q.astype(ck.dtype), kk, vv, mask_fn, cfg.attn_softcap)
    o = o.reshape(b, c, cfg.n_heads * cfg.head_dim).astype(x.dtype)
    out = linear(p["o_proj"], gather_heads(o, mesh))

    # write the chunk into the cache (last writer wins on ring collisions)
    idx = (positions % length).astype(jnp.int32)
    ok = paged.chunk_write_plan(idx, valid_tok, length)
    wpos = positions.astype(jnp.int32)
    if kv_quant:
        # scatter the qs/d computed up front — never quantize twice
        new = {
            "k_qs": paged.scatter_chunk(cache["k_qs"], block_table, idx,
                                        k_qs, ok),
            "k_d": paged.scatter_chunk(cache["k_d"], block_table, idx,
                                       k_d, ok),
            "v_qs": paged.scatter_chunk(cache["v_qs"], block_table, idx,
                                        v_qs, ok),
            "v_d": paged.scatter_chunk(cache["v_d"], block_table, idx,
                                       v_d, ok),
            "pos": paged.scatter_chunk(cache["pos"], block_table, idx,
                                       wpos, ok),
        }
    elif block_table is not None:
        new = {
            "k": paged.scatter_chunk(cache["k"], block_table, idx, k, ok),
            "v": paged.scatter_chunk(cache["v"], block_table, idx, v, ok),
            "pos": paged.scatter_chunk(cache["pos"], block_table, idx,
                                       wpos, ok),
        }
    else:
        bidx = jnp.arange(b)[:, None]
        idx_w = jnp.where(ok, idx, length)         # out-of-bounds -> dropped
        new = {
            "k": ck.at[bidx, idx_w].set(k.astype(ck.dtype), mode="drop"),
            "v": cv.at[bidx, idx_w].set(v.astype(cv.dtype), mode="drop"),
            "pos": cpos.at[bidx, idx_w].set(wpos, mode="drop"),
        }
    return out, new


def _attend_cache(cfg: ModelConfig, q: jax.Array, ck: jax.Array,
                  cv: jax.Array, cpos: jax.Array, pos: jax.Array, *,
                  local: bool) -> jax.Array:
    """One rotated query row against a dense cache view — the masked
    softmax read path shared by :func:`attn_decode` and the quantized
    gather reference.  q: (B, 1, H, D); ck/cv: (B, L, Hkv, D); cpos:
    (B, L); returns (B, 1, H*D) attended output (pre-``o_proj``, f32
    accumulated)."""
    b = q.shape[0]
    rep = cfg.n_heads // cfg.n_kv_heads
    scale = cfg.head_dim ** -0.5
    valid = (cpos >= 0) & (cpos <= pos[:, None])
    if local and cfg.window:
        valid &= cpos > (pos[:, None] - cfg.window)
    if GQA_EINSUM:
        qg = (q[:, 0] * scale).reshape(b, cfg.n_kv_heads, rep, cfg.head_dim)
        s = jnp.einsum("bkrd,blkd->bkrl", qg, ck,
                       preferred_element_type=jnp.float32)
        if cfg.attn_softcap:
            s = softcap(s, cfg.attn_softcap)
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkrl,blkd->bkrd", w.astype(cv.dtype), cv,
                       preferred_element_type=jnp.float32)
    else:
        kk = jnp.repeat(ck.astype(jnp.float32), rep, axis=2)
        vv = jnp.repeat(cv.astype(jnp.float32), rep, axis=2)
        s = jnp.einsum("bhd,blhd->bhl",
                       q[:, 0].astype(jnp.float32) * scale, kk)
        if cfg.attn_softcap:
            s = softcap(s, cfg.attn_softcap)
        s = jnp.where(valid[:, None, :], s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhl,blhd->bhd", w, vv)
    return o.reshape(b, 1, cfg.n_heads * cfg.head_dim)


def attn_decode(p: dict, cfg: ModelConfig, x: jax.Array, cache: dict,
                pos: jax.Array, *, local: bool,
                live: jax.Array | None = None) -> tuple[jax.Array, dict]:
    """One-token decode.  x: (B, 1, D); pos: (B,) absolute position.

    ``live`` (B,) bool: rows flagged False (free / mid-prefill lanes in a
    batched serve step) drop their cache write, so throwaway decode rows
    can never corrupt a lane whose prompt is still streaming in.
    """
    b = x.shape[0]
    h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
    q, k, v = _qkv(p, cfg, h, pos[:, None])
    length = cache["k"].shape[1]
    slot = (pos % length).astype(jnp.int32)
    wslot = slot if live is None else jnp.where(live, slot, length)
    bidx = jnp.arange(b)
    ck = cache["k"].at[bidx, wslot].set(k[:, 0].astype(cache["k"].dtype),
                                        mode="drop")
    cv = cache["v"].at[bidx, wslot].set(v[:, 0].astype(cache["v"].dtype),
                                        mode="drop")
    cpos = cache["pos"].at[bidx, wslot].set(pos.astype(jnp.int32),
                                            mode="drop")
    o = _attend_cache(cfg, q, ck, cv, cpos, pos, local=local).astype(x.dtype)
    out = linear(p["o_proj"], o)
    return out, {"k": ck, "v": cv, "pos": cpos}
