"""Per-layer forward / decode dispatch across all block families."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from . import attention, mla, moe, rglru, xlstm
from .common import ffn_apply, linear, rms_norm, swiglu
from .paged import resolve_layer_quant


def _cross_kv(cp: dict, cfg: ModelConfig, enc_hidden: jax.Array):
    """Project encoder hidden states with this layer's cross K/V weights."""
    b, t, _ = enc_hidden.shape
    nkv, hd = cfg.n_kv_heads, cfg.head_dim
    k = linear(cp["k_proj"], enc_hidden).reshape(b, t, nkv, hd)
    v = linear(cp["v_proj"], enc_hidden).reshape(b, t, nkv, hd)
    return k, v


def apply_layer(cfg: ModelConfig, p: dict, layer: int, x: jax.Array,
                *, positions=None, enc_hidden=None, causal: bool = True):
    """Full-sequence layer (train/prefill).  Returns (x, aux_loss)."""
    kind = cfg.block_kind(layer)
    aux = jnp.zeros((), jnp.float32)

    if kind in ("attn", "local_attn"):
        if cfg.mla:
            x = x + mla.mla_forward(p, cfg, x, positions)
        else:
            x = x + attention.attn_forward(
                p, cfg, x, local=(kind == "local_attn"), positions=positions,
                causal=causal)
    elif kind == "rglru":
        x = x + rglru.rglru_forward(p, cfg, x)
    elif kind == "mlstm":
        return x + xlstm.mlstm_forward(p, cfg, x), aux
    elif kind == "slstm":
        return x + xlstm.slstm_block(p, cfg, x), aux
    else:
        raise ValueError(kind)

    if enc_hidden is not None:
        from .spec import subview
        cp = subview(p, "cross")
        x = x + attention.attn_forward(
            cp, cfg, x, local=False, kv_override=_cross_kv(cp, cfg, enc_hidden),
            causal=False)

    if cfg.d_ff == 0 and not cfg.is_moe:
        return x, aux

    if cfg.moe_layer(layer):
        h = rms_norm(x, p["ffn_norm"], cfg.norm_eps)
        y, aux = moe.moe_apply(p, cfg, h)
        if cfg.dense_residual:
            from .spec import subview
            rp = subview(p, "res")
            hr = rms_norm(x, rp["ffn_norm"], cfg.norm_eps)
            y = y + ffn_apply(rp, hr)
        x = x + y
    else:
        h = rms_norm(x, p["ffn_norm"], cfg.norm_eps)
        x = x + ffn_apply(p, h)
    return x, aux


def _select_live(cache_new: dict, cache_old: dict, live) -> dict:
    """Keep non-live rows' state untouched (recurrent passthrough leaves:
    every leaf has a leading batch dim)."""
    if live is None:
        return cache_new
    out = {}
    for k, v in cache_new.items():
        m = live.reshape(live.shape[0], *([1] * (v.ndim - 1)))
        out[k] = jnp.where(m, v, cache_old[k])
    return out


def decode_layer(cfg: ModelConfig, p: dict, layer: int, x: jax.Array,
                 cache: dict, pos: jax.Array, *, paged=None, live=None):
    """One-token decode through one layer.  Returns (x, new_cache).

    ``paged``: optional ``(block_tables, page_size, max_len, kernel,
    active_pages, kv_quant, lane_pages, mesh)`` — attention and MLA caches
    are then page pools indexed through the slot block tables
    (``block_tables["full"]`` / ``["ring"]``); recurrent state is a dense
    passthrough either way.  ``kernel`` picks fused-Pallas vs
    gather-reference decode (None = env default); ``active_pages`` is an
    optional ``(n_full, n_ring)`` static bound on the page loop for the
    fused kernel and ``lane_pages`` an optional ``{"full": (B,), "ring":
    (B,)}`` per-lane refinement of it; ``kv_quant`` selects the quantized
    pool layout — ``"q8_0"``/``"q4_0"`` uniformly, ``"dq"`` per layer via
    :func:`repro.models.paged.resolve_layer_quant` (the matching fused
    quantized kernels are picked automatically).  ``live`` (B,) bool:
    rows flagged False (free / mid-prefill serve lanes) leave the cache
    untouched.
    """
    kind = cfg.block_kind(layer)
    cross = {k: cache.pop(k) for k in ("cross_k", "cross_v")
             if k in cache} if cfg.is_encdec else {}

    if kind in ("attn", "local_attn"):
        local = kind == "local_attn"
        if paged is not None:
            (block_tables, _, max_len, kernel, active, kv_quant,
             lane_pages, mesh) = paged
            kv_quant = resolve_layer_quant(kv_quant, cfg, layer)
            # MLA latents always span the full horizon (no ring bound)
            use_ring = local and not cfg.mla
            tbl_kind = "ring" if use_ring else "full"
            bt = block_tables[tbl_kind]
            ap = None
            if active is not None:
                ap = active[1] if use_ring else active[0]
                ap = ap or None
            lp = lane_pages[tbl_kind] if lane_pages is not None else None
        with jax.named_scope("attn"):
            if paged is not None and cfg.mla:
                delta, cache_new = mla.mla_decode_paged(
                    p, cfg, x, cache, pos, bt, max_len=max_len, live=live,
                    kernel=kernel, active_pages=ap, lane_pages=lp,
                    kv_quant=kv_quant, mesh=mesh)
            elif paged is not None:
                delta, cache_new = attention.attn_decode_paged(
                    p, cfg, x, cache, pos, bt, local=local,
                    max_len=max_len, live=live, kernel=kernel,
                    active_pages=ap, lane_pages=lp, kv_quant=kv_quant,
                    mesh=mesh)
            elif cfg.mla:
                delta, cache_new = mla.mla_decode(p, cfg, x, cache, pos,
                                                  live=live)
            else:
                delta, cache_new = attention.attn_decode(
                    p, cfg, x, cache, pos, local=local, live=live)
        x = x + delta
    elif kind == "rglru":
        delta, cache_new = rglru.rglru_decode(p, cfg, x, cache, pos)
        cache_new = _select_live(cache_new, cache, live)
        x = x + delta
    elif kind == "mlstm":
        delta, cache_new = xlstm.mlstm_decode(p, cfg, x, cache, pos)
        return x + delta, _select_live(cache_new, cache, live)
    elif kind == "slstm":
        delta, cache_new = xlstm.slstm_decode(p, cfg, x, cache, pos)
        return x + delta, _select_live(cache_new, cache, live)
    else:
        raise ValueError(kind)

    if cross:
        from .spec import subview
        cp = subview(p, "cross")
        x = x + _cross_decode(cp, cfg, x, (cross["cross_k"], cross["cross_v"]))
        cache_new = dict(cache_new, **cross)

    if cfg.d_ff == 0 and not cfg.is_moe:
        return x, cache_new

    if cfg.moe_layer(layer):
        h = rms_norm(x, p["ffn_norm"], cfg.norm_eps)
        y, _ = moe.moe_apply(p, cfg, h)
        if cfg.dense_residual:
            from .spec import subview
            rp = subview(p, "res")
            hr = rms_norm(x, rp["ffn_norm"], cfg.norm_eps)
            y = y + ffn_apply(rp, hr)
        x = x + y
    else:
        h = rms_norm(x, p["ffn_norm"], cfg.norm_eps)
        x = x + ffn_apply(p, h)
    return x, cache_new


def _cross_decode(cp: dict, cfg: ModelConfig, x: jax.Array, enc_out):
    """Cross-attention for a single decode token (no cache mutation —
    encoder K/V are precomputed in ``enc_out``)."""
    k, v = enc_out
    b = x.shape[0]
    nh, hd = cfg.n_heads, cfg.head_dim
    h = rms_norm(x, cp["attn_norm"], cfg.norm_eps)
    q = linear(cp["q_proj"], h).reshape(b, 1, nh, hd)
    rep = nh // cfg.n_kv_heads
    kk = jnp.repeat(k, rep, axis=2)
    vv = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,blhd->bhql", q, kk,
                   preferred_element_type=jnp.float32) * hd ** -0.5
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhql,blhd->bqhd", w.astype(vv.dtype), vv,
                   preferred_element_type=jnp.float32)
    o = o.reshape(b, 1, nh * hd).astype(x.dtype)
    return linear(cp["o_proj"], o)


def prefill_layer(cfg: ModelConfig, p: dict, layer: int, x: jax.Array,
                  max_len: int, *, enc_hidden=None):
    """Full-sequence forward that also builds this layer's decode cache."""
    kind = cfg.block_kind(layer)

    if kind in ("attn", "local_attn"):
        if cfg.mla:
            delta, cache = mla.mla_prefill(p, cfg, x, max_len)
        else:
            delta, cache = attention.attn_prefill(
                p, cfg, x, max_len, local=(kind == "local_attn"))
        x = x + delta
    elif kind == "rglru":
        delta, cache = rglru.rglru_prefill(p, cfg, x, max_len)
        x = x + delta
    elif kind == "mlstm":
        delta, cache = xlstm.mlstm_prefill(p, cfg, x, max_len)
        return x + delta, cache
    elif kind == "slstm":
        delta, cache = xlstm.slstm_prefill(p, cfg, x, max_len)
        return x + delta, cache
    else:
        raise ValueError(kind)

    if enc_hidden is not None:
        from .spec import subview
        cp = subview(p, "cross")
        ck, cv = _cross_kv(cp, cfg, enc_hidden)
        x = x + attention.attn_forward(
            cp, cfg, x, local=False, kv_override=(ck, cv), causal=False)
        cache = dict(cache, cross_k=ck, cross_v=cv)

    if cfg.d_ff == 0 and not cfg.is_moe:
        return x, cache

    if cfg.moe_layer(layer):
        h = rms_norm(x, p["ffn_norm"], cfg.norm_eps)
        y, _ = moe.moe_apply(p, cfg, h)
        if cfg.dense_residual:
            from .spec import subview
            rp = subview(p, "res")
            hr = rms_norm(x, rp["ffn_norm"], cfg.norm_eps)
            y = y + ffn_apply(rp, hr)
        x = x + y
    else:
        h = rms_norm(x, p["ffn_norm"], cfg.norm_eps)
        x = x + ffn_apply(p, h)
    return x, cache


def prefill_chunk_layer(cfg: ModelConfig, p: dict, layer: int, x: jax.Array,
                        cache: dict, positions: jax.Array, start: jax.Array,
                        chunk_len: jax.Array, *, max_len: int, paged=None):
    """One prefill chunk through one layer against the pooled cache.

    x: (B, C, D) right-padded per row; ``chunk_len`` (B,) counts valid
    tokens (0 = inactive row).  Returns (x, new_layer_cache).  Same
    ``paged`` contract as :func:`decode_layer`.
    """
    kind = cfg.block_kind(layer)
    if cfg.is_encdec:
        raise ValueError("chunked prefill does not support encoder-decoder "
                         "architectures (no cross-attention cache build)")

    if kind in ("attn", "local_attn"):
        local = kind == "local_attn"
        bt, kv_quant, kernel, ap, mesh = None, None, None, None, None
        if paged is not None:
            block_tables, _, _, kv_quant, kernel, active, mesh = paged
            kv_quant = resolve_layer_quant(kv_quant, cfg, layer)
            # MLA latents always span the full horizon (no ring bound)
            use_ring = local and not cfg.mla
            bt = block_tables["ring" if use_ring else "full"]
            if active is not None:
                ap = active[1] if use_ring else active[0]
                ap = ap or None
        with jax.named_scope("attn"):
            if cfg.mla:
                delta, cache_new = mla.mla_prefill_chunk(
                    p, cfg, x, cache, positions, start, chunk_len,
                    max_len=max_len, block_table=bt, kv_quant=kv_quant,
                    kernel=kernel, active_pages=ap, mesh=mesh)
            else:
                delta, cache_new = attention.attn_prefill_chunk(
                    p, cfg, x, cache, positions, start, chunk_len,
                    local=local, max_len=max_len, block_table=bt,
                    kv_quant=kv_quant, kernel=kernel, active_pages=ap,
                    mesh=mesh)
        x = x + delta
    elif kind == "rglru":
        delta, cache_new = rglru.rglru_prefill_chunk(
            p, cfg, x, cache, start, chunk_len)
        x = x + delta
    elif kind == "mlstm":
        delta, cache_new = xlstm.mlstm_prefill_chunk(
            p, cfg, x, cache, start, chunk_len)
        return x + delta, cache_new
    elif kind == "slstm":
        delta, cache_new = xlstm.slstm_prefill_chunk(
            p, cfg, x, cache, start, chunk_len)
        return x + delta, cache_new
    else:
        raise ValueError(kind)

    if cfg.d_ff == 0 and not cfg.is_moe:
        return x, cache_new

    if cfg.moe_layer(layer):
        h = rms_norm(x, p["ffn_norm"], cfg.norm_eps)
        y, _ = moe.moe_apply(p, cfg, h)
        if cfg.dense_residual:
            from .spec import subview
            rp = subview(p, "res")
            hr = rms_norm(x, rp["ffn_norm"], cfg.norm_eps)
            y = y + ffn_apply(rp, hr)
        x = x + y
    else:
        h = rms_norm(x, p["ffn_norm"], cfg.norm_eps)
        x = x + ffn_apply(p, h)
    return x, cache_new


def init_layer_cache_paged(cfg: ModelConfig, layer: int, num_pages: int,
                           page_size: int, slots: int,
                           dtype=jnp.bfloat16,
                           kv_quant: str | None = None) -> dict:
    """Paged layer cache: attention/MLA leaves become page pools; recurrent
    state stays a dense ``(slots, ...)`` passthrough (O(1) per slot).
    ``kv_quant`` switches the positional pools to the quantized layout —
    resolved per layer, so under ``"dq"`` sensitive layers keep q8_0
    leaves while the rest pack q4_0 nibbles (recurrent passthrough state
    is never quantized)."""
    kind = cfg.block_kind(layer)
    if cfg.is_encdec:
        raise ValueError("paged caches do not support encoder-decoder "
                         "architectures")
    if kind in ("attn", "local_attn"):
        lq = resolve_layer_quant(kv_quant, cfg, layer)
        if cfg.mla:
            return mla.init_paged_mla_cache(cfg, num_pages, page_size, dtype,
                                            kv_quant=lq)
        return attention.init_paged_attn_cache(cfg, num_pages, page_size,
                                               dtype, kv_quant=lq)
    if kind == "rglru":
        return rglru.init_rglru_cache(cfg, slots, dtype)
    if kind == "mlstm":
        return xlstm.init_mlstm_cache(cfg, slots, dtype)
    if kind == "slstm":
        return xlstm.init_slstm_cache(cfg, slots, dtype)
    raise ValueError(kind)


def layer_cache_specs_paged(cfg: ModelConfig, layer: int, num_pages: int,
                            page_size: int, slots: int,
                            dtype=jnp.bfloat16,
                            kv_quant: str | None = None) -> dict:
    kind = cfg.block_kind(layer)
    if cfg.is_encdec:
        raise ValueError("paged caches do not support encoder-decoder "
                         "architectures")
    if kind in ("attn", "local_attn"):
        lq = resolve_layer_quant(kv_quant, cfg, layer)
        if cfg.mla:
            return mla.paged_mla_cache_specs(cfg, num_pages, page_size,
                                             dtype, kv_quant=lq)
        return attention.paged_attn_cache_specs(cfg, num_pages, page_size,
                                                dtype, kv_quant=lq)
    if kind == "rglru":
        return rglru.rglru_cache_specs(cfg, slots, dtype)
    if kind == "mlstm":
        return xlstm.mlstm_cache_specs(cfg, slots, dtype)
    if kind == "slstm":
        return xlstm.slstm_cache_specs(cfg, slots, dtype)
    raise ValueError(kind)


def init_layer_cache(cfg: ModelConfig, layer: int, batch: int, max_len: int,
                     dtype=jnp.bfloat16) -> dict:
    kind = cfg.block_kind(layer)
    if kind in ("attn", "local_attn"):
        if cfg.mla:
            cache = mla.init_mla_cache(cfg, batch, max_len, dtype)
        else:
            cache = attention.init_attn_cache(
                cfg, batch, max_len, kind == "local_attn", dtype)
    elif kind == "rglru":
        cache = rglru.init_rglru_cache(cfg, batch, dtype)
    elif kind == "mlstm":
        cache = xlstm.init_mlstm_cache(cfg, batch, dtype)
    elif kind == "slstm":
        cache = xlstm.init_slstm_cache(cfg, batch, dtype)
    else:
        raise ValueError(kind)
    if cfg.is_encdec and kind in ("attn", "local_attn"):
        t_enc = cfg.frontend_tokens
        z = jnp.zeros((batch, t_enc, cfg.n_kv_heads, cfg.head_dim), dtype)
        cache = dict(cache, cross_k=z, cross_v=z)
    return cache


def layer_cache_specs(cfg: ModelConfig, layer: int, batch: int, max_len: int,
                      dtype=jnp.bfloat16) -> dict:
    kind = cfg.block_kind(layer)
    if kind in ("attn", "local_attn"):
        if cfg.mla:
            cache = mla.mla_cache_specs(cfg, batch, max_len, dtype)
        else:
            cache = attention.attn_cache_specs(
                cfg, batch, max_len, kind == "local_attn", dtype)
    elif kind == "rglru":
        cache = rglru.rglru_cache_specs(cfg, batch, dtype)
    elif kind == "mlstm":
        cache = xlstm.mlstm_cache_specs(cfg, batch, dtype)
    elif kind == "slstm":
        cache = xlstm.slstm_cache_specs(cfg, batch, dtype)
    else:
        raise ValueError(kind)
    if cfg.is_encdec and kind in ("attn", "local_attn"):
        t_enc = cfg.frontend_tokens
        sds = jax.ShapeDtypeStruct(
            (batch, t_enc, cfg.n_kv_heads, cfg.head_dim), dtype)
        cache = dict(cache, cross_k=sds, cross_v=sds)
    return cache
