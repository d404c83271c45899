"""Multi-head Latent Attention (DeepSeek V2/V3) with compressed KV cache.

Prefill/train materialises per-head K/V from the low-rank latents (the
"naive" evaluation) and reuses the chunked flash attention.  Decode uses the
**absorbed** form: the cache stores only the 512-d compressed latent ``c_kv``
plus the 64-d decoupled RoPE key per token — the deployment-critical memory
saving behind the paper's Table-1 "MU @32k context" numbers — and the
``kv_b`` projection is folded into the query/output paths so no per-head K/V
is ever materialised at decode time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..kernels import paged_attn
from . import paged
from .attention import (_chunk_attn, causal_mask_fn, chunk_key_positions,
                        chunk_mask_fn, default_paged_kernel, NEG_INF)
from .common import apply_rope, gather_heads, linear, rms_norm

from ..core.qtensor import QTensor


def _maybe_dequant(w, dtype):
    if isinstance(w, QTensor):
        return w.dequantize(dtype)
    return w.astype(dtype)


def _project_q(p, cfg: ModelConfig, h, positions):
    b, t, _ = h.shape
    nh = cfg.n_heads
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    cq = rms_norm(linear(p["q_a"], h), p["q_a_norm"], cfg.norm_eps)
    q = linear(p["q_b"], cq).reshape(b, t, nh, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _latents(p, cfg: ModelConfig, h, positions):
    b, t, _ = h.shape
    dr = cfg.qk_rope_head_dim
    kv = linear(p["kv_a"], h)                                 # (B,T,rank+dr)
    c_kv = rms_norm(kv[..., : cfg.kv_lora_rank], p["kv_a_norm"], cfg.norm_eps)
    k_rope = kv[..., cfg.kv_lora_rank:]                       # (B,T,dr)
    k_rope = apply_rope(k_rope[..., None, :], positions,
                        cfg.rope_theta)[..., 0, :]
    return c_kv, k_rope


def mla_forward(p: dict, cfg: ModelConfig, x: jax.Array,
                positions=None) -> jax.Array:
    """Train/prefill MLA.  x: (B, T, D)."""
    b, t, _ = x.shape
    nh = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
    if positions is None:
        positions = jnp.arange(t)[None, :]
    q_nope, q_rope = _project_q(p, cfg, h, positions)
    c_kv, k_rope = _latents(p, cfg, h, positions)
    kvb = linear(p["kv_b"], c_kv).reshape(b, t, nh, dn + dv)
    k_nope, v = kvb[..., :dn], kvb[..., dn:]
    # decoupled-rope key is shared across heads (MQA-style)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)            # (B,T,H,dn+dr)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, t, nh, dr))],
        axis=-1)
    o = _chunk_attn(q, k, v, causal_mask_fn(), 0.0)
    o = o.reshape(b, t, nh * dv).astype(x.dtype)
    return linear(p["o_proj"], o)


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16) -> dict:
    return {
        "c_kv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_head_dim), dtype),
    }


def mla_cache_specs(cfg: ModelConfig, batch: int, max_len: int,
                    dtype=jnp.bfloat16) -> dict:
    return {
        "c_kv": jax.ShapeDtypeStruct((batch, max_len, cfg.kv_lora_rank), dtype),
        "k_rope": jax.ShapeDtypeStruct(
            (batch, max_len, cfg.qk_rope_head_dim), dtype),
    }


def mla_prefill(p: dict, cfg: ModelConfig, x: jax.Array,
                max_len: int) -> tuple[jax.Array, dict]:
    """Full-sequence MLA forward that also fills the compressed cache."""
    b, t, _ = x.shape
    positions = jnp.arange(t)[None, :]
    h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
    c_kv, k_rope = _latents(p, cfg, h, positions)
    out = mla_forward(p, cfg, x, positions)
    cache = init_mla_cache(cfg, b, max_len, dtype=c_kv.dtype)
    cache = {
        "c_kv": cache["c_kv"].at[:, :t].set(c_kv),
        "k_rope": cache["k_rope"].at[:, :t].set(k_rope),
    }
    return out, cache


def _latent_widths(cfg: ModelConfig, lq: "paged.LayerQuant"):
    """Stored trailing dims of the latent/rope qs leaves for a layer's
    quant assignment — halved (nibble-packed) for q4_0 leaves."""
    rank_s = (paged.q4_packed_dim(cfg.kv_lora_rank, "latent rank")
              if lq.latent == "q4_0" else cfg.kv_lora_rank)
    dr_s = (paged.q4_packed_dim(cfg.qk_rope_head_dim, "rope dim")
            if lq.kv == "q4_0" else cfg.qk_rope_head_dim)
    return rank_s, dr_s


def init_paged_mla_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                         dtype=jnp.bfloat16, kv_quant=None) -> dict:
    """Paged latent pools; validity is positional (idx <= pos), so no pos
    pool is needed — unallocated logical pages gather NULL_PAGE zeros that
    the mask never attends.  ``kv_quant`` (a mode string or a per-layer
    :class:`repro.models.paged.LayerQuant`): int8 latent/rope pools plus
    one f32 scale per (page, token) row (block = the latent/rope width);
    q4_0 leaves store two nibbles per byte so the qs trailing dim is
    halved.  NULL-page zeros dequantize to the same never-written zeros."""
    if kv_quant:
        lq = paged.as_layer_quant(kv_quant)
        rank_s, dr_s = _latent_widths(cfg, lq)
        return {
            "c_kv_qs": jnp.zeros((num_pages, page_size, rank_s), jnp.int8),
            "c_kv_d": jnp.zeros((num_pages, page_size), jnp.float32),
            "k_rope_qs": jnp.zeros((num_pages, page_size, dr_s), jnp.int8),
            "k_rope_d": jnp.zeros((num_pages, page_size), jnp.float32),
        }
    return {
        "c_kv": jnp.zeros((num_pages, page_size, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((num_pages, page_size, cfg.qk_rope_head_dim),
                            dtype),
    }


def paged_mla_cache_specs(cfg: ModelConfig, num_pages: int, page_size: int,
                          dtype=jnp.bfloat16, kv_quant=None) -> dict:
    if kv_quant:
        lq = paged.as_layer_quant(kv_quant)
        rank_s, dr_s = _latent_widths(cfg, lq)
        return {
            "c_kv_qs": jax.ShapeDtypeStruct(
                (num_pages, page_size, rank_s), jnp.int8),
            "c_kv_d": jax.ShapeDtypeStruct((num_pages, page_size),
                                           jnp.float32),
            "k_rope_qs": jax.ShapeDtypeStruct(
                (num_pages, page_size, dr_s), jnp.int8),
            "k_rope_d": jax.ShapeDtypeStruct((num_pages, page_size),
                                             jnp.float32),
        }
    return {
        "c_kv": jax.ShapeDtypeStruct(
            (num_pages, page_size, cfg.kv_lora_rank), dtype),
        "k_rope": jax.ShapeDtypeStruct(
            (num_pages, page_size, cfg.qk_rope_head_dim), dtype),
    }


def mla_decode_paged(p: dict, cfg: ModelConfig, x: jax.Array, cache: dict,
                     pos: jax.Array, block_table: jax.Array, *,
                     max_len: int, live: jax.Array | None = None,
                     kernel: str | None = None,
                     active_pages: int | None = None,
                     lane_pages: jax.Array | None = None,
                     kv_quant=None,
                     mesh=None,
                     ) -> tuple[jax.Array, dict]:
    """Absorbed decode against paged latents.

    ``kernel="fused"`` (default) scatters the new latent row into its page
    and attends the pages in place with the flash-decode Pallas kernel —
    scores and accumulation stay in the compressed latent space, the
    absorbed ``kv_b`` projections are applied outside the kernel.
    ``kernel="gather"`` is the reference: gather the exact dense view, run
    the unchanged :func:`mla_decode`, scatter the new row back.

    ``kv_quant`` (a mode string or a per-layer
    :class:`repro.models.paged.LayerQuant` — under the "dq" policy the
    latent leaf stays q8_0 even when the rope leaf drops to q4_0) expects
    the quantized pool layout of :func:`init_paged_mla_cache`: the new
    latent/rope row is quantized before the write, so fused (in-kernel
    dequant) and gather (dequantizing gather + :func:`_absorbed_attend`)
    see the same round-tripped values.
    """
    kernel = kernel or default_paged_kernel()
    if kernel not in ("fused", "gather"):
        raise ValueError(f"unknown paged decode kernel {kernel!r}")
    lq = paged.as_layer_quant(kv_quant) if kv_quant else None
    if kernel == "gather" and not kv_quant:
        dense = {k: gather_heads(
                     paged.gather_pages(cache[k], block_table, max_len), mesh)
                 for k in ("c_kv", "k_rope")}
        delta, dnew = mla_decode(p, cfg, x, dense, pos, live=live)
        bidx = jnp.arange(x.shape[0])
        new = {k: paged.scatter_token(cache[k], block_table, pos,
                                      dnew[k][bidx, pos], ok=live)
               for k in ("c_kv", "k_rope")}
        return delta, new

    b = x.shape[0]
    nh = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    rank = cfg.kv_lora_rank
    h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
    q_nope, q_rope = _project_q(p, cfg, h, pos[:, None])      # (B,1,H,*)
    c_new, kr_new = _latents(p, cfg, h, pos[:, None])         # (B,1,rank)
    idx = pos.astype(jnp.int32)
    if kv_quant:
        cq, cd = paged.scatter_token_quant(cache["c_kv_qs"], cache["c_kv_d"],
                                           block_table, idx, c_new[:, 0],
                                           ok=live, mode=lq.latent)
        kq, kd = paged.scatter_token_quant(cache["k_rope_qs"],
                                           cache["k_rope_d"], block_table,
                                           idx, kr_new[:, 0], ok=live,
                                           mode=lq.kv)
        new = {"c_kv_qs": cq, "c_kv_d": cd, "k_rope_qs": kq, "k_rope_d": kd}
        if kernel == "gather":
            # keep the dequantized views in f32 — the fused kernel also
            # dequantizes in f32, so the reference must not round through
            # the model dtype on bf16 deployments
            ckv = paged.gather_pages_quant(cq, cd, block_table, max_len,
                                           lq.latent)
            krope = paged.gather_pages_quant(kq, kd, block_table, max_len,
                                             lq.kv)
            return _absorbed_attend(p, cfg, x.dtype, q_nope, q_rope,
                                    gather_heads(ckv, mesh),
                                    gather_heads(krope, mesh), pos), new
    else:
        new = {
            "c_kv": paged.scatter_token(cache["c_kv"], block_table, idx,
                                        c_new[:, 0], ok=live),
            "k_rope": paged.scatter_token(cache["k_rope"], block_table, idx,
                                          kr_new[:, 0], ok=live),
        }
    dt = x.dtype
    w_kvb = _maybe_dequant(p["kv_b"], dt).reshape(rank, nh, dn + dv)
    w_kb, w_vb = w_kvb[..., :dn], w_kvb[..., dn:]
    q_eff = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0].astype(jnp.float32),
                       w_kb.astype(jnp.float32))              # (B,H,rank)
    if kv_quant:
        lat = paged_attn.paged_mla_decode_quant(
            q_eff.astype(dt), q_rope[:, 0], cq, cd, kq, kd,
            block_table, pos, scale=(dn + dr) ** -0.5,
            latent_mode=lq.latent, rope_mode=lq.kv,
            active_pages=active_pages, lane_pages=lane_pages, mesh=mesh)
    else:
        lat = paged_attn.paged_mla_decode(
            q_eff.astype(dt), q_rope[:, 0], new["c_kv"], new["k_rope"],
            block_table, pos, scale=(dn + dr) ** -0.5,
            active_pages=active_pages, lane_pages=lane_pages, mesh=mesh)
    o = jnp.einsum("bhr,rhd->bhd", lat.astype(dt), w_vb,
                   preferred_element_type=jnp.float32)        # (B,H,dv)
    o = o.reshape(b, 1, nh * dv).astype(x.dtype)
    return linear(p["o_proj"], gather_heads(o, mesh)), new


def mla_prefill_chunk(p: dict, cfg: ModelConfig, x: jax.Array, cache: dict,
                      positions: jax.Array, start: jax.Array,
                      chunk_len: jax.Array, *, max_len: int,
                      block_table: jax.Array | None = None,
                      kv_quant=None, kernel: str | None = None,
                      active_pages: int | None = None, mesh=None,
                      ) -> tuple[jax.Array, dict]:
    """One prefill chunk against the compressed-latent cache.

    Materialises per-head K/V from [cached latents | chunk latents] (the
    naive evaluation, as in :func:`mla_forward`) and attends the chunk
    queries over it with per-row positional masks; writes the chunk's
    latents into the cache (dense rows or pages; quantized rows when
    ``kv_quant`` — the chunk's latents are quantized once up front and
    attended through the same round trip they are stored with, so outputs
    are chunk-size independent).

    ``kernel="fused"`` on a quantized cache runs the *write-then-attend*
    absorbed path: the quantized latent rows are scattered into their
    pages first, then every chunk query attends the packed pools in place
    (:func:`repro.kernels.paged_attn.paged_mla_prefill_quant`) — no dense
    dequantised latent view is ever materialised.  ``kernel="gather"``
    keeps the naive-materialisation reference path.  ``mesh``: the
    serving mesh, forwarded to the fused kernel (runs it under
    ``shard_map``).
    """
    b, c, _ = x.shape
    nh = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    lq = paged.as_layer_quant(kv_quant) if kv_quant else None
    kernel = kernel or default_paged_kernel()
    h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
    q_nope, q_rope = _project_q(p, cfg, h, positions)
    c_new, kr_new = _latents(p, cfg, h, positions)

    if kv_quant and kernel == "fused":
        # write-then-attend absorbed prefill: quantize once, scatter,
        # attend the packed pools in place (scores and accumulation stay
        # in the compressed latent space, as in the fused decode)
        valid_tok = jnp.arange(c)[None, :] < chunk_len[:, None]    # (B, C)
        idx = positions.astype(jnp.int32)
        c_qs, c_d = paged.quantize_rows(c_new, lq.latent)
        kr_qs, kr_d = paged.quantize_rows(kr_new, lq.kv)
        new = {
            "c_kv_qs": paged.scatter_chunk(cache["c_kv_qs"], block_table,
                                           idx, c_qs, valid_tok),
            "c_kv_d": paged.scatter_chunk(cache["c_kv_d"], block_table,
                                          idx, c_d, valid_tok),
            "k_rope_qs": paged.scatter_chunk(cache["k_rope_qs"], block_table,
                                             idx, kr_qs, valid_tok),
            "k_rope_d": paged.scatter_chunk(cache["k_rope_d"], block_table,
                                            idx, kr_d, valid_tok),
        }
        qpos = jnp.where(valid_tok, positions, -1).astype(jnp.int32)
        dt = x.dtype
        rank = cfg.kv_lora_rank
        w_kvb = _maybe_dequant(p["kv_b"], dt).reshape(rank, nh, dn + dv)
        w_kb, w_vb = w_kvb[..., :dn], w_kvb[..., dn:]
        q_eff = jnp.einsum("bchd,rhd->bchr", q_nope.astype(jnp.float32),
                           w_kb.astype(jnp.float32))          # (B,C,H,rank)
        lat = paged_attn.paged_mla_prefill_quant(
            q_eff.astype(dt), q_rope, new["c_kv_qs"], new["c_kv_d"],
            new["k_rope_qs"], new["k_rope_d"], block_table, qpos,
            scale=(dn + dr) ** -0.5, latent_mode=lq.latent,
            rope_mode=lq.kv, active_pages=active_pages, mesh=mesh)
        o = jnp.einsum("bchr,rhd->bchd", lat.astype(dt), w_vb,
                       preferred_element_type=jnp.float32)
        o = o.reshape(b, c, nh * dv).astype(x.dtype)
        return linear(p["o_proj"], gather_heads(o, mesh)), new

    c_qs = c_d = kr_qs = kr_d = None
    if kv_quant:
        assert block_table is not None, "kv_quant requires paged caches"
        ckv = paged.gather_pages_quant(cache["c_kv_qs"], cache["c_kv_d"],
                                       block_table, max_len, lq.latent)
        krope = paged.gather_pages_quant(cache["k_rope_qs"],
                                         cache["k_rope_d"], block_table,
                                         max_len, lq.kv)
        # quantize the chunk's latents once, up front: in-chunk attention
        # uses the round-tripped view and the same qs/d are scattered
        # below, so in-chunk and cross-chunk reads see identical values
        # whatever the chunk size
        c_qs, c_d, c_att = paged.roundtrip_quant(c_new, lq.latent)
        kr_qs, kr_d, kr_att = paged.roundtrip_quant(kr_new, lq.kv)
    elif block_table is not None:
        ckv = paged.gather_pages(cache["c_kv"], block_table, max_len)
        krope = paged.gather_pages(cache["k_rope"], block_table, max_len)
        c_att, kr_att = c_new, kr_new
    else:
        ckv, krope = cache["c_kv"], cache["k_rope"]
        c_att, kr_att = c_new, kr_new

    valid_tok = jnp.arange(c)[None, :] < chunk_len[:, None]        # (B, C)
    ckv_all = jnp.concatenate([ckv, c_att.astype(ckv.dtype)], axis=1)
    kr_all = jnp.concatenate([krope, kr_att.astype(krope.dtype)], axis=1)
    # cache entries carry their logical index (latents store no positions)
    old_pos = jnp.broadcast_to(
        jnp.arange(max_len, dtype=jnp.int32)[None, :], (b, max_len))
    key_pos = chunk_key_positions(old_pos, positions, valid_tok)
    mask_fn = chunk_mask_fn(key_pos, max_len, positions, start, 0)

    kvb = linear(p["kv_b"], ckv_all).reshape(b, max_len + c, nh, dn + dv)
    k_nope, v = kvb[..., :dn], kvb[..., dn:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kr_all[:, :, None, :],
                                  (b, max_len + c, nh, dr))], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)

    o = _chunk_attn(q, k, v, mask_fn, 0.0)
    o = o.reshape(b, c, nh * dv).astype(x.dtype)
    out = linear(p["o_proj"], gather_heads(o, mesh))

    idx = positions.astype(jnp.int32)
    ok = valid_tok                          # full horizon: no ring collisions
    if kv_quant:
        # scatter the qs/d computed up front — never quantize twice
        new = {
            "c_kv_qs": paged.scatter_chunk(cache["c_kv_qs"], block_table,
                                           idx, c_qs, ok),
            "c_kv_d": paged.scatter_chunk(cache["c_kv_d"], block_table,
                                          idx, c_d, ok),
            "k_rope_qs": paged.scatter_chunk(cache["k_rope_qs"], block_table,
                                             idx, kr_qs, ok),
            "k_rope_d": paged.scatter_chunk(cache["k_rope_d"], block_table,
                                            idx, kr_d, ok),
        }
    elif block_table is not None:
        new = {
            "c_kv": paged.scatter_chunk(cache["c_kv"], block_table, idx,
                                        c_new, ok),
            "k_rope": paged.scatter_chunk(cache["k_rope"], block_table, idx,
                                          kr_new, ok),
        }
    else:
        bidx = jnp.arange(b)[:, None]
        idx_w = jnp.where(ok, idx, max_len)
        new = {
            "c_kv": ckv.at[bidx, idx_w].set(c_new.astype(ckv.dtype),
                                            mode="drop"),
            "k_rope": krope.at[bidx, idx_w].set(kr_new.astype(krope.dtype),
                                                mode="drop"),
        }
    return out, new


def _absorbed_attend(p: dict, cfg: ModelConfig, dt, q_nope: jax.Array,
                     q_rope: jax.Array, c_kv: jax.Array, k_rope: jax.Array,
                     pos: jax.Array) -> jax.Array:
    """Absorbed-form attention of one query row over dense latent views —
    the read path shared by :func:`mla_decode` and the quantized gather
    reference.  q_nope/q_rope: (B, 1, H, *); c_kv: (B, L, rank); k_rope:
    (B, L, dr); returns the projected output (B, 1, H*dv) in ``dt``."""
    b = q_nope.shape[0]
    nh = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    rank = cfg.kv_lora_rank
    # absorb kv_b: W_kb (rank, H, dn) for keys, W_vb (rank, H, dv) for values
    w_kvb = _maybe_dequant(p["kv_b"], dt).reshape(rank, nh, dn + dv)
    w_kb, w_vb = w_kvb[..., :dn], w_kvb[..., dn:]
    # q_eff[h] = q_nope[h] @ W_kb[h]^T  -> compare directly against c_kv
    q_eff = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0].astype(jnp.float32),
                       w_kb.astype(jnp.float32))              # (B,H,rank)
    scale = (dn + dr) ** -0.5
    s = (jnp.einsum("bhr,blr->bhl", q_eff.astype(dt), c_kv,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhd,bld->bhl", q_rope[:, 0], k_rope,
                      preferred_element_type=jnp.float32)) * scale
    valid = jnp.arange(c_kv.shape[1])[None, :] <= pos[:, None]
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    # attend in latent space, then project out with W_vb
    lat = jnp.einsum("bhl,blr->bhr", w.astype(dt), c_kv,
                     preferred_element_type=jnp.float32)      # (B,H,rank)
    o = jnp.einsum("bhr,rhd->bhd", lat.astype(dt), w_vb,
                   preferred_element_type=jnp.float32)        # (B,H,dv)
    o = o.reshape(b, 1, nh * dv).astype(dt)
    return linear(p["o_proj"], o)


def mla_decode(p: dict, cfg: ModelConfig, x: jax.Array, cache: dict,
               pos: jax.Array,
               live: jax.Array | None = None) -> tuple[jax.Array, dict]:
    """Absorbed one-token decode.  x: (B, 1, D); pos: (B,).

    ``live`` (B,) bool: rows flagged False drop their cache write (see
    :func:`repro.models.attention.attn_decode`).
    """
    b = x.shape[0]
    h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
    q_nope, q_rope = _project_q(p, cfg, h, pos[:, None])      # (B,1,H,*)
    c_new, kr_new = _latents(p, cfg, h, pos[:, None])         # (B,1,rank)

    length = cache["c_kv"].shape[1]
    wpos = pos if live is None else jnp.where(live, pos, length)
    bidx = jnp.arange(b)
    c_kv = cache["c_kv"].at[bidx, wpos].set(
        c_new[:, 0].astype(cache["c_kv"].dtype), mode="drop")
    k_rope = cache["k_rope"].at[bidx, wpos].set(
        kr_new[:, 0].astype(cache["k_rope"].dtype), mode="drop")
    out = _absorbed_attend(p, cfg, x.dtype, q_nope, q_rope, c_kv, k_rope,
                           pos)
    return out, {"c_kv": c_kv, "k_rope": k_rope}
