"""Model: the public forward / loss / prefill / decode API over all archs.

Two execution modes share the same per-layer code:

  * ``scan=False`` (eager/unrolled): per-layer flat params; any policy mix;
    used by tests, examples and the quality benchmarks (small models).
  * ``scan=True``: parameters stacked by :mod:`.stacking` groups and the
    layer stack executed with ``jax.lax.scan`` (+ optional remat) — one trace
    per repeating unit, which keeps compile time bounded for the 35-80-layer
    full configs in the multi-pod dry-run.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import InputShape, ModelConfig
from ..core.policy import Policy
from . import stacking, transformer
from .common import embed, linear, rms_norm, softcap
from .spec import layer_prefix, subview


@dataclasses.dataclass
class Model:
    cfg: ModelConfig
    scan: bool = False
    plan: stacking.StackPlan | None = None   # required when scan=True
    remat: bool = False
    dtype: Any = jnp.bfloat16
    # NamedSharding for (B, T, D) activations; pinning this stops the SPMD
    # partitioner from "helpfully" resharding activations to match FSDP
    # weight shardings (observed 35 GB/layer of activation all-gathers
    # otherwise — EXPERIMENTS.md §Perf).
    act_shard: Any = None

    def __post_init__(self):
        if self.scan and self.plan is None:
            self.plan = stacking.plan(self.cfg)

    def _wsc(self, x):
        if self.act_shard is not None:
            return jax.lax.with_sharding_constraint(x, self.act_shard)
        return x

    # ------------------------------------------------------------------ embed
    def _embed_tokens(self, params, tokens):
        with jax.named_scope("embed"):
            x = embed(params["token_embd"], tokens, self.dtype)
            if self.cfg.embed_scale:
                x = x * jnp.asarray(
                    jnp.sqrt(self.cfg.d_model), x.dtype)
        return x

    def _fuse_frontend(self, params, batch):
        """Returns (x (B,T,D), enc_hidden or None, n_prefix_tokens)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        x = self._embed_tokens(params, tokens)
        if cfg.frontend == "vit":
            patches = batch["patches"]                   # (B, P, front_dim)
            front = rms_norm(patches.astype(jnp.float32),
                             params["mm_proj_norm"], cfg.norm_eps)
            front = linear(params["mm_proj"], front.astype(x.dtype))
            x = jnp.concatenate([front, x], axis=1)
            return x, None, cfg.frontend_tokens
        if cfg.is_encdec:
            frames = batch["frames"]                     # (B, F, front_dim)
            enc_in = linear(params["frontend_proj"], frames.astype(x.dtype))
            enc_hidden = self._run_encoder(params, enc_in)
            return x, enc_hidden, 0
        return x, None, 0

    # ---------------------------------------------------------------- encoder
    def _run_encoder(self, params, x):
        cfg = self.cfg
        if not self.scan:
            for layer in range(cfg.encoder_layers):
                p = subview(params, layer_prefix("enc", layer))
                x, _ = transformer.apply_layer(cfg, p, layer, x, causal=False)
        else:
            x, _ = self._scan_stack(params, x, "enc", positions=None,
                                    enc_hidden=None, causal=False)
        return rms_norm(x, params["enc/output_norm"], cfg.norm_eps)

    # ---------------------------------------------------------------- forward
    def _scan_stack(self, params, x, stack, *, positions, enc_hidden, causal):
        cfg = self.cfg
        groups = (self.plan.dec_groups if stack == "dec"
                  else self.plan.enc_groups)
        aux_total = jnp.zeros((), jnp.float32)
        for gi, g in enumerate(groups):
            unit_params = {u: stacking.group_view(params, stack, gi, u)
                           for u in range(g.unit)}

            def body(carry, pslice, _g=g, _unit=unit_params):
                xc = carry
                aux = jnp.zeros((), jnp.float32)
                for u in range(_g.unit):
                    layer = _g.layer(0, u)   # structural twin of every rep
                    xc, a = transformer.apply_layer(
                        cfg, pslice[u], layer, xc, positions=positions,
                        enc_hidden=enc_hidden, causal=causal)
                    xc = self._wsc(xc)
                    aux = aux + a
                return xc, aux

            if self.remat:
                body = jax.checkpoint(
                    body, policy=jax.checkpoint_policies.nothing_saveable)
            x, auxs = jax.lax.scan(body, x, unit_params)
            aux_total = aux_total + jnp.sum(auxs)
        return x, aux_total

    def hidden_states(self, params, batch):
        """Full forward up to the final norm.  Returns (hidden, aux, n_front)."""
        cfg = self.cfg
        x, enc_hidden, n_front = self._fuse_frontend(params, batch)
        x = self._wsc(x)
        positions = jnp.arange(x.shape[1])[None, :]
        if self.scan:
            x, aux = self._scan_stack(params, x, "dec", positions=positions,
                                      enc_hidden=enc_hidden, causal=True)
        else:
            aux = jnp.zeros((), jnp.float32)
            for layer in range(cfg.n_layers):
                p = subview(params, layer_prefix("dec", layer))
                x, a = transformer.apply_layer(
                    cfg, p, layer, x, positions=positions,
                    enc_hidden=enc_hidden)
                aux = aux + a
        x = rms_norm(x, params["output_norm"], cfg.norm_eps)
        return x, aux, n_front

    def logits(self, params, hidden):
        cfg = self.cfg
        w = params["token_embd"] if cfg.tie_embeddings else params["output"]
        with jax.named_scope("lm_head"):
            out = linear(w, hidden)
            out = softcap(out, cfg.logit_softcap)
        return out[..., : cfg.vocab_size]

    def forward(self, params, batch):
        hidden, aux, n_front = self.hidden_states(params, batch)
        if n_front:
            hidden = hidden[:, n_front:]
        return self.logits(params, hidden), aux

    def loss(self, params, batch):
        """Next-token cross entropy (+ MoE aux).  batch['labels']: (B, T)."""
        logits, aux = self.forward(params, batch)
        labels = batch["labels"]
        lf = logits.astype(jnp.float32)
        logz = jax.scipy.special.logsumexp(lf, axis=-1)
        gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
        mask = (labels >= 0).astype(jnp.float32)
        nll = jnp.sum((logz - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        total = nll + self.cfg.router_aux_loss * aux
        return total, {"nll": nll, "aux": aux}

    # ---------------------------------------------------------------- serving
    def prefill(self, params, batch, max_len: int, *, lengths=None):
        """Forward + decode-cache build.  Returns (last_logits, cache).

        ``lengths`` ((B,) int32, optional): true prompt lengths for a
        right-padded batch.  When given, the returned logits are gathered at
        position ``lengths - 1`` per row instead of the last *padded*
        position, so mixed-length batches sample their first token from the
        correct hidden state.  (Padded positions still land in the decode
        cache, but decode masks entries beyond ``pos`` and overwrites each
        position before attending to it, so they are never read.)
        """
        cfg = self.cfg
        x, enc_hidden, n_front = self._fuse_frontend(params, batch)
        cache: dict[str, Any] = {}
        if not self.scan:
            for layer in range(cfg.n_layers):
                p = subview(params, layer_prefix("dec", layer))
                x, c = transformer.prefill_layer(
                    cfg, p, layer, x, max_len, enc_hidden=enc_hidden)
                for k, v in c.items():
                    cache[f"{layer_prefix('dec', layer)}/{k}"] = v
        else:
            for gi, g in enumerate(self.plan.dec_groups):
                unit_params = {u: stacking.group_view(params, "dec", gi, u)
                               for u in range(g.unit)}

                def body(carry, pslice, _g=g):
                    xc = carry
                    caches = {}
                    for u in range(_g.unit):
                        layer = _g.layer(0, u)
                        xc, c = transformer.prefill_layer(
                            cfg, pslice[u], layer, xc, max_len,
                            enc_hidden=enc_hidden)
                        caches[u] = c
                    return xc, caches

                x, caches = jax.lax.scan(body, x, unit_params)
                for u, c in caches.items():
                    for k, v in c.items():
                        cache[f"{stacking.group_prefix('dec', gi)}/u{u}/{k}"] = v
        x = rms_norm(x, params["output_norm"], cfg.norm_eps)
        if lengths is None:
            last_h = x[:, -1:]
        else:
            idx = (jnp.asarray(lengths, jnp.int32) + n_front - 1)
            last_h = jnp.take_along_axis(x, idx[:, None, None], axis=1)
        last = self.logits(params, last_h)
        return last, cache

    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        flat = {}
        for layer in range(self.cfg.n_layers):
            c = transformer.init_layer_cache(
                self.cfg, layer, batch, max_len, dtype)
            for k, v in c.items():
                flat[f"{layer_prefix('dec', layer)}/{k}"] = v
        if self.scan:
            flat = stacking.stack_tree(flat, self.plan)
        return flat

    def cache_specs(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        flat = {}
        for layer in range(self.cfg.n_layers):
            c = transformer.layer_cache_specs(
                self.cfg, layer, batch, max_len, dtype)
            for k, v in c.items():
                flat[f"{layer_prefix('dec', layer)}/{k}"] = v
        if self.scan:
            flat = stacking.stack_tree(flat, self.plan)
        return flat

    def decode_step(self, params, cache, tokens, pos, *, paged=None,
                    live=None):
        """One decode step.  tokens: (B,) int32; pos: (B,).

        Returns (logits (B, vocab), new_cache).  ``live`` (B,) bool: rows
        flagged False compute a throwaway step whose cache writes are
        dropped (used by the serve loop so free / mid-prefill lanes never
        corrupt pooled state).  ``paged`` (internal): see
        :meth:`decode_step_paged`.
        """
        cfg = self.cfg
        x = self._embed_tokens(params, tokens[:, None])
        new_cache: dict[str, Any] = {}
        if not self.scan:
            for layer in range(cfg.n_layers):
                lp = layer_prefix("dec", layer)
                p = subview(params, lp)
                c = subview(cache, lp)
                x, c_new = transformer.decode_layer(cfg, p, layer, x, c, pos,
                                                    paged=paged, live=live)
                for k, v in c_new.items():
                    new_cache[f"{lp}/{k}"] = v
        else:
            for gi, g in enumerate(self.plan.dec_groups):
                unit_params = {u: stacking.group_view(params, "dec", gi, u)
                               for u in range(g.unit)}
                unit_cache = {
                    u: stacking.group_view(cache, "dec", gi, u)
                    for u in range(g.unit)}

                def body(carry, inp, _g=g):
                    xc = carry
                    pslice, cslice = inp
                    out_caches = {}
                    for u in range(_g.unit):
                        layer = _g.layer(0, u)
                        xc, c_new = transformer.decode_layer(
                            cfg, pslice[u], layer, xc, dict(cslice[u]), pos,
                            paged=paged, live=live)
                        out_caches[u] = c_new
                    return xc, out_caches

                x, caches = jax.lax.scan(body, x, (unit_params, unit_cache))
                for u, c in caches.items():
                    for k, v in c.items():
                        new_cache[
                            f"{stacking.group_prefix('dec', gi)}/u{u}/{k}"] = v
        x = rms_norm(x, params["output_norm"], cfg.norm_eps)
        return self.logits(params, x)[:, 0], new_cache

    # ---------------------------------------------------------------- paged
    def init_paged_cache(self, num_pages: int, page_size: int, slots: int,
                         dtype=jnp.bfloat16, kv_quant: str | None = None):
        """Paged decode cache: attention K/V (+pos) and MLA latents become
        ``(num_pages, page_size, ...)`` pools shared by all slots via block
        tables; recurrent state stays dense ``(slots, ...)`` (O(1)/slot).
        ``kv_quant="q8_0"`` stores the positional pools as int8 + per-row
        f32 scales (~4x less cache memory; see models/paged.py);
        ``"q4_0"`` packs two int4 codes per byte (~8x); ``"dq"`` assigns
        bitwidths per layer (sensitive layers stay q8_0)."""
        self._check_paged_quant(kv_quant)
        flat = {}
        for layer in range(self.cfg.n_layers):
            c = transformer.init_layer_cache_paged(
                self.cfg, layer, num_pages, page_size, slots, dtype,
                kv_quant=kv_quant)
            for k, v in c.items():
                flat[f"{layer_prefix('dec', layer)}/{k}"] = v
        if self.scan:
            flat = stacking.stack_tree(flat, self.plan)
        return flat

    def _check_paged_quant(self, kv_quant):
        if self.scan and kv_quant == "dq":
            raise ValueError(
                "kv_quant='dq' assigns bitwidths per layer, which is "
                "incompatible with scan=True: stacked layer groups share "
                "one leaf layout (use a uniform mode such as 'q8_0' or "
                "'q4_0' with scan)")

    def paged_cache_specs(self, num_pages: int, page_size: int, slots: int,
                          dtype=jnp.bfloat16, kv_quant: str | None = None):
        self._check_paged_quant(kv_quant)
        flat = {}
        for layer in range(self.cfg.n_layers):
            c = transformer.layer_cache_specs_paged(
                self.cfg, layer, num_pages, page_size, slots, dtype,
                kv_quant=kv_quant)
            for k, v in c.items():
                flat[f"{layer_prefix('dec', layer)}/{k}"] = v
        if self.scan:
            flat = stacking.stack_tree(flat, self.plan)
        return flat

    def decode_step_paged(self, params, cache, tokens, pos, block_tables,
                          *, page_size: int, max_len: int, live=None,
                          kernel: str | None = None,
                          active_pages: tuple[int, int] | None = None,
                          lane_pages=None,
                          kv_quant: str | None = None,
                          mesh=None):
        """One decode step against a paged cache.

        ``block_tables``: {"full": (B, n) int32, "ring": (B, n') int32}
        mapping each slot's logical pages to pool pages (see
        models/paged.py).  ``kernel`` selects the per-layer paged decode:
        ``"fused"`` (default via ``REPRO_PAGED_KERNEL``) runs the Pallas
        flash-decode kernels that read pages in place;  ``"gather"`` is the
        reference path, bitwise-identical to :meth:`decode_step` on the
        equivalent dense cache (gathers the exact dense view and runs the
        same per-layer decode on it).  ``active_pages``: optional static
        ``(n_full_pages, n_ring_pages)`` bound on the fused kernels' page
        loops — the serve loop passes the batch's bucketed live horizon so
        decode bandwidth scales with live tokens.  ``lane_pages``:
        optional ``{"full": (B,), "ring": (B,)}`` int32 per-lane live page
        counts, a further per-lane refinement of ``active_pages`` (a short
        lane's fused-kernel reads then stop scaling with the batch's
        longest lane).  ``kv_quant``: the cache quantization spec the
        pools were initialised with (``"q8_0"``, ``"q4_0"`` or the
        per-layer ``"dq"`` policy) — the matching fused quantized
        kernels (or dequantizing gather reference) are selected
        automatically.
        ``mesh``: the device mesh the engine serves on (``None`` =
        single-device) — forwarded to the fused kernels, which run under
        ``shard_map`` on it so sharded pool operands stay correct.
        """
        return self.decode_step(
            params, cache, tokens, pos,
            paged=(block_tables, page_size, max_len, kernel, active_pages,
                   kv_quant, lane_pages, mesh),
            live=live)

    def prefill_chunk(self, params, cache, tokens, start, chunk_len, *,
                      max_len: int, block_tables=None, page_size: int = 0,
                      kv_quant: str | None = None,
                      kernel: str | None = None,
                      active_pages: tuple[int, int] | None = None,
                      mesh=None):
        """One chunked-prefill step over the pooled decode cache.

        tokens: (B, C) int32, right-padded per row; start: (B,) absolute
        position of each row's first token; chunk_len: (B,) valid tokens
        (0 = inactive row — no cache writes, output ignored).  Rows whose
        chunk starts at position 0 reset their recurrent state.  Returns
        (logits (B, vocab) at each row's last valid position, new_cache).

        With ``block_tables``/``page_size`` the cache is paged (and
        ``kv_quant`` selects the quantized pool layout, resolved per
        layer under ``"dq"``); otherwise it is the dense pooled layout of
        :meth:`init_cache`.  ``kernel="fused"`` (default via
        ``REPRO_PAGED_KERNEL``) runs quantized full-horizon layers through
        the write-then-attend prefill kernels — packed pages stay packed;
        ``"gather"`` keeps the dequantizing-gather reference.
        ``active_pages``: optional static ``(n_full, n_ring)`` bound on
        the fused prefill kernels' page loops, and ``mesh`` the serving
        mesh the fused kernels run on, both as in :meth:`decode_step_paged`.
        """
        cfg = self.cfg
        if cfg.frontend == "vit" or cfg.is_encdec:
            raise ValueError("chunked prefill supports decoder-only text "
                             "models (no frontend fusion mid-stream)")
        if kv_quant and block_tables is None:
            raise ValueError("kv_quant requires a paged cache "
                             "(pass block_tables/page_size)")
        self._check_paged_quant(kv_quant)
        paged = (None if block_tables is None
                 else (block_tables, page_size, max_len, kv_quant, kernel,
                       active_pages, mesh))
        c = tokens.shape[1]
        x = self._embed_tokens(params, tokens)
        positions = start[:, None] + jnp.arange(c)[None, :]
        new_cache: dict[str, Any] = {}
        if not self.scan:
            for layer in range(cfg.n_layers):
                lp = layer_prefix("dec", layer)
                x, c_new = transformer.prefill_chunk_layer(
                    cfg, subview(params, lp), layer, x, subview(cache, lp),
                    positions, start, chunk_len, max_len=max_len, paged=paged)
                for k, v in c_new.items():
                    new_cache[f"{lp}/{k}"] = v
        else:
            for gi, g in enumerate(self.plan.dec_groups):
                unit_params = {u: stacking.group_view(params, "dec", gi, u)
                               for u in range(g.unit)}
                unit_cache = {
                    u: stacking.group_view(cache, "dec", gi, u)
                    for u in range(g.unit)}

                def body(carry, inp, _g=g):
                    xc = carry
                    pslice, cslice = inp
                    out_caches = {}
                    for u in range(_g.unit):
                        layer = _g.layer(0, u)
                        xc, c_new = transformer.prefill_chunk_layer(
                            cfg, pslice[u], layer, xc, dict(cslice[u]),
                            positions, start, chunk_len, max_len=max_len,
                            paged=paged)
                        out_caches[u] = c_new
                    return xc, out_caches

                x, caches = jax.lax.scan(body, x, (unit_params, unit_cache))
                for u, cc in caches.items():
                    for k, v in cc.items():
                        new_cache[
                            f"{stacking.group_prefix('dec', gi)}/u{u}/{k}"] = v
        x = rms_norm(x, params["output_norm"], cfg.norm_eps)
        idx = jnp.clip(chunk_len - 1, 0, c - 1).astype(jnp.int32)
        last_h = jnp.take_along_axis(x, idx[:, None, None], axis=1)
        return self.logits(params, last_h)[:, 0], new_cache


# ---------------------------------------------------------------------------
# input specs for the assigned shape matrix
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: InputShape) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of one cell."""
    b, t = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    if shape.kind in ("train", "prefill"):
        specs: dict[str, Any] = {}
        t_text = t
        if cfg.frontend == "vit":
            t_text = t - cfg.frontend_tokens
            specs["patches"] = jax.ShapeDtypeStruct(
                (b, cfg.frontend_tokens, cfg.frontend_dim), jnp.bfloat16)
        if cfg.is_encdec:
            specs["frames"] = jax.ShapeDtypeStruct(
                (b, cfg.frontend_tokens, cfg.frontend_dim), jnp.bfloat16)
        specs["tokens"] = jax.ShapeDtypeStruct((b, t_text), i32)
        if shape.kind == "train":
            specs["labels"] = jax.ShapeDtypeStruct((b, t_text), i32)
        return specs
    # decode: one new token against a length-t cache
    return {
        "tokens": jax.ShapeDtypeStruct((b,), i32),
        "pos": jax.ShapeDtypeStruct((b,), i32),
    }
