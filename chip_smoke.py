#!/usr/bin/env python3
"""Smoke test on a TPU: serve full-width qwen2-1.5b through the fused
paged-attention kernels.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # 2x2 mesh serve vs one chip

Everything runs in this one process, which holds the chip(s).

Default (one chip): qwen2-1.5b at its published widths, all 28 layers,
random weights from seed 0, quantized with DQ3_K_M through
``launch/serve.py``'s own steps (``load_quantized`` -> ``serve_requests``
-> ``Engine.serve``).  Then, for f32 pools (``--dtype float32``) and for
q8_0 pools (bf16 activations):

  1. serve 8 requests over 4 slots (page 16, max_len 512, 16 new tokens,
     greedy, ``kernel="fused"``); every request must end ``ok`` with 16
     tokens inside the vocabulary;
  2. require a ``tpu_custom_call`` in the compiled fused decode step: the
     Pallas kernel, not the XLA twin, is what runs;
  3. teacher-force that serve's own steps and a ``kernel="gather"``
     engine's (the reference) through one prefill chunk of PROMPT tokens
     per lane and STEPS decode steps, both reading the reference's cache,
     and require the logits to agree to LOGIT_RTOL and the argmaxes to
     AGREE_FLOOR; a planted fault (the fused step reading the cache with
     its two KV heads swapped, as a wrong head split would) must exceed
     LOGIT_RTOL;
  4. run the fused decode kernel alone on random f32, bf16 and q8_0 pages
     at the model's widths (lanes of 37 to 512 tokens) against its XLA
     twin at HIGHEST matmul precision, and require KERNEL_RTOL; the
     head-swapped kernel must exceed it.  The kernel is also run on each
     KV-head slice alone, as each device runs it under ``Engine(mesh=)``,
     and its gap to the whole kernel's heads is printed.

``--four-chips`` runs only this: the same serve with q8_0 pools on a
``2x2`` (data, model) mesh, where the model axis of 2 divides the 2 KV
heads so the fused kernels run head-split under ``shard_map``; the same
requests on one of those chips with no mesh; step 3 with the mesh serve's
steps as the test and the one-chip serve's as the reference, held to
MESH_RTOL; and the greedy outputs of every request, which must be equal.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU, or when any check fails, the script exits non-zero and
prints no such line.  The compilation cache goes to
``$JAX_COMPILATION_CACHE_DIR`` if set, else ``<repo>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels import paged_attn  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import paged  # noqa: E402
from repro.serving.engine import Engine  # noqa: E402

SERVE_ARGV = ["--arch", "qwen2-1.5b", "--policy", "DQ3_K_M",
              "--page-size", "16", "--kernel", "fused", "--requests", "8",
              "--slots", "4", "--max-len", "512", "--max-new", "16",
              "--temperature", "0", "--seed", "0"]
PROMPT = 8          # teacher-forced prompt tokens per lane: one chunk
STEPS = 24          # teacher-forced decode steps: crosses a page boundary
# Largest admitted max|test - ref| / max|ref| over one lane's logits, fused
# vs gather.  Both round each layer's activations to the model dtype and
# sum in different orders (the gather reference also rounds its attention
# weights to the model dtype); 28 layers of random weights amplify that.
# Measured 0.10 to 0.11 for bf16 and q8_0 pools on one TPU v5e; a kernel
# that drops the lane's last page gave 0.9 to 1.6 on the CPU.
LOGIT_RTOL = 0.25
AGREE_FLOOR = 0.75  # least share of lanes x steps whose argmax agrees
# The mesh step runs the one-chip step's program on every device, with the
# attention kernel on its own KV-head slice and every weight contraction
# whole (tests/test_tpu_compile.py holds it to no all-reduce): bit for bit.
MESH_RTOL = 0.0
# Largest admitted max|kernel - XLA twin| / max|twin| of one decode
# attention; the twin runs at HIGHEST precision.  Measured 0.0038 (bf16
# pages) and 0.0028 (q8_0) against a default-precision twin on one TPU v5e.
KERNEL_RTOL = 1e-2
_KV_LEAVES = ("k", "v", "k_qs", "k_d", "v_qs", "v_d")


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def serve_args(**overrides) -> argparse.Namespace:
    args = serve.parse_args(SERVE_ARGV)
    for k, v in overrides.items():
        setattr(args, k, v)
    return args


def check_served(tag: str, args, cfg, done) -> dict[int, list[int]]:
    """Every request finished ``ok`` with ``max_new`` in-vocab tokens."""
    check(len(done) == args.requests,
          f"{tag}: {len(done)} of {args.requests} requests came back")
    for r in sorted(done, key=lambda r: r.rid):
        print(f"[{tag}] req {r.rid}: status {r.status}, {len(r.out)} tokens")
        check(r.status == "ok", f"{tag}: request {r.rid} ended {r.status!r}")
        check(len(r.out) == args.max_new,
              f"{tag}: request {r.rid} made {len(r.out)} tokens, "
              f"expected {args.max_new}")
        check(all(0 <= t < cfg.vocab_size for t in r.out),
              f"{tag}: request {r.rid} has tokens outside the vocabulary")
    return {r.rid: list(r.out) for r in done}


def swap_kv_heads(cache: dict) -> dict:
    """The planted fault: every K/V pool leaf with its KV-head axis
    reversed, as a kernel handed the wrong head slice would read it."""
    return {k: jnp.flip(v, axis=2) if k.rsplit("/", 1)[-1] in _KV_LEAVES
            else v for k, v in cache.items()}


def _gap(test, ref) -> np.ndarray:
    """Per-lane max|test - ref| / max|ref| of (lanes, vocab) logits."""
    t, r = (np.asarray(x, np.float32) for x in (test, ref))
    check(bool(np.isfinite(t).all() and np.isfinite(r).all()),
          "non-finite logits")
    return np.abs(t - r).max(-1) / np.abs(r).max(-1)


def compare_steps(tag: str, test: Engine, ref: Engine, slots: int, seed: int,
                  *, rtol: float, agree_floor: float) -> None:
    """Teacher-force two engines' jitted serving steps (a serve that has
    run already traced them: nothing compiles again) through one prefill
    chunk and STEPS decode steps, each step from the reference's cache,
    and compare their logits; check that the test engine's compiled
    decode step holds the Pallas kernel, and that the planted fault is
    caught."""
    model, p = ref.model, ref.page_size
    n = paged.pages_for(ref.max_len, p)
    pages = {e: e.pool_pages(slots) for e in (test, ref)}

    def placed(eng, cache):
        # the pool as this engine lays it out: its own page count (a mesh
        # pads it; padding pages are never in a block table) and sharding
        extra = pages[eng] - pages[ref]
        cache = {k: jnp.pad(v, [(0, extra)] + [(0, 0)] * (v.ndim - 1),
                            constant_values=-1 if k.endswith("/pos") else 0)
                 for k, v in cache.items()}
        if eng.mesh is None:
            return cache
        return jax.device_put(cache, eng._cache_shardings)

    # each lane owns n consecutive pages; no ring layers in this model
    tables = {"full": jnp.asarray(
                  paged.RESERVED_PAGES
                  + np.arange(slots * n, dtype=np.int32).reshape(slots, n)),
              "ring": jnp.full((slots, 1), paged.GARBAGE_PAGE, jnp.int32)}
    cache = model.init_paged_cache(pages[ref], p, slots, dtype=model.dtype,
                                   kv_quant=ref.kv_quant)
    rng = np.random.default_rng(seed)
    toks = np.zeros((slots, ref.prefill_chunk), np.int32)
    toks[:, :PROMPT] = rng.integers(4, model.cfg.vocab_size, (slots, PROMPT))
    chunk = (jnp.asarray(toks), jnp.zeros((slots,), jnp.int32),
             jnp.full((slots,), PROMPT, jnp.int32))
    out = {e: e._chunk(e.params, placed(e, cache), *chunk,
                       block_tables=tables) for e in (test, ref)}
    gaps = {"prefill": _gap(out[test][0], out[ref][0])}
    agree = int((np.asarray(out[test][0]).argmax(-1)
                 == np.asarray(out[ref][0]).argmax(-1)).sum())
    cache = out[ref][1]

    live = jnp.ones((slots,), jnp.bool_)
    decode_gaps = []
    for t in range(STEPS):
        pos = PROMPT + t
        npg = paged.pages_for(pos + 1, p)
        step = (jnp.asarray(rng.integers(4, model.cfg.vocab_size, slots),
                            jnp.int32),
                jnp.full((slots,), pos, jnp.int32), tables)
        out = {}
        for e in (ref, test):
            kw = dict(live=live, active_pages=None, lane_pages=None)
            if e.kernel == "fused":
                # the serve's own page bucket and per-lane page counts
                kw.update(active_pages=(1 << (npg - 1).bit_length(), 0),
                          lane_pages={"full": jnp.full((slots,), npg,
                                                       jnp.int32),
                                      "ring": jnp.zeros((slots,), jnp.int32)})
            out[e] = e._decode_paged(e.params, placed(e, cache), *step, **kw)
        decode_gaps.append(_gap(out[test][0], out[ref][0]))
        agree += int((np.asarray(out[test][0]).argmax(-1)
                      == np.asarray(out[ref][0]).argmax(-1)).sum())
        if t < STEPS - 1:
            cache = out[ref][1]
    # the last step again: compiled text, and the planted fault
    kernels = test._decode_paged.lower(
        test.params, placed(test, cache), *step, **kw).compile(
        ).as_text().count("tpu_custom_call")
    fault = _gap(test._decode_paged(test.params,
                                    placed(test, swap_kv_heads(cache)),
                                    *step, **kw)[0], out[ref][0])
    worst = max(float(gaps["prefill"].max()),
                max(float(g.max()) for g in decode_gaps))
    total = slots * (STEPS + 1)
    print(f"[{tag}] tpu_custom_call in the compiled test decode step: "
          f"{kernels}")
    print(f"[{tag}] relative logit gap: prefill "
          f"{float(gaps['prefill'].max())!r}, decode over {STEPS} steps "
          f"{max(float(g.max()) for g in decode_gaps)!r} (limit {rtol}); "
          f"argmax agreement {agree}/{total} (floor {agree_floor}); "
          f"planted fault (KV heads swapped) {float(fault.max())!r}")
    check(kernels > 0, f"{tag}: no Pallas kernel in the fused decode step")
    check(worst <= rtol, f"{tag}: test logits differ from the reference's "
                         f"by {worst!r} relative")
    check(agree >= agree_floor * total,
          f"{tag}: argmax agrees on {agree}/{total} only")
    check(float(fault.max()) > rtol,
          f"{tag}: the planted fault stays within the limit")


def _kernel(q, kv, tables, mode, impl, lanes):
    """One fused decode attention over (K, V) pools in ``mode`` ("f32",
    "bf16" or a quantized mode)."""
    common = dict(active_pages=tables[1].shape[1], lane_pages=lanes,
                  impl=impl)
    k, v = kv
    if mode in ("f32", "bf16"):
        dt = jnp.float32 if mode == "f32" else jnp.bfloat16
        return paged_attn.paged_attn_decode(q, k.astype(dt), v.astype(dt),
                                            *tables, **common)
    kq, kd = paged.quantize_rows(k, mode)
    vq, vd = paged.quantize_rows(v, mode)
    return paged_attn.paged_attn_decode_quant(q, kq, kd, vq, vd, *tables,
                                              mode=mode, **common)


def kernel_vs_twin(args, cfg) -> None:
    """The fused GQA decode kernel against its XLA twin on one chip."""
    h, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    rep = h // hkv
    slots, p = args.slots, args.page_size
    n = paged.pages_for(args.max_len, p)
    num_pages = paged.RESERVED_PAGES + slots * n
    lengths = np.linspace(37, args.max_len, slots).astype(np.int32)
    bt = paged.RESERVED_PAGES + np.arange(slots * n, dtype=np.int32).reshape(
        slots, n)
    pos_pool = np.full((num_pages, p), -1, np.int32)
    for s, length in enumerate(lengths):
        for j in range(paged.pages_for(int(length), p)):
            pos_pool[bt[s, j]] = np.arange(j * p, (j + 1) * p)
    rng = np.random.default_rng(args.seed)
    kv = [jnp.asarray(rng.normal(size=(num_pages, p, hkv, d)), jnp.float32)
          for _ in range(2)]
    q = jnp.asarray(rng.normal(size=(slots, h, d)), jnp.float32)
    lanes = jnp.asarray([paged.pages_for(int(x), p) for x in lengths],
                        jnp.int32)
    tables = (jnp.asarray(pos_pool), jnp.asarray(bt),
              jnp.asarray(lengths - 1))
    for mode in ("f32", "bf16", "q8_0"):
        got = np.asarray(_kernel(q, kv, tables, mode, "pallas", lanes))
        with jax.default_matmul_precision("highest"):
            want = np.asarray(_kernel(q, kv, tables, mode, "xla", lanes))
        bad = np.asarray(_kernel(q, [jnp.flip(x, axis=2) for x in kv],
                                 tables, mode, "pallas", lanes))
        # each device's share under a head-split mesh: one KV head
        part = np.concatenate(
            [np.asarray(_kernel(q[:, j * rep:(j + 1) * rep],
                                [x[:, :, j:j + 1] for x in kv], tables, mode,
                                "pallas", lanes)) for j in range(hkv)],
            axis=1)
        scale = np.abs(want).max()
        gap = float(np.abs(got - want).max() / scale)
        fault = float(np.abs(bad - want).max() / scale)
        split = float(np.abs(part - got).max() / scale)
        print(f"[kernel {mode}] Pallas decode vs XLA twin (HIGHEST), lanes "
              f"{lengths.tolist()}: relative gap {gap!r} (limit "
              f"{KERNEL_RTOL}); KV heads swapped {fault!r}; per-KV-head "
              f"slices vs whole kernel {split!r}")
        check(gap <= KERNEL_RTOL,
              f"{mode}: decode kernel differs from its XLA twin by {gap!r}")
        check(fault > KERNEL_RTOL,
              f"{mode}: the planted fault stays within the limit")
        check(split <= KERNEL_RTOL,
              f"{mode}: the kernel on KV-head slices differs by {split!r}")


def one_chip() -> None:
    args = serve_args()
    t0 = time.perf_counter()
    cfg, qparams = serve.load_quantized(args)
    jax.block_until_ready(qparams)
    print(f"init + quantize: {time.perf_counter() - t0:.1f} s")
    for dtype, kv_quant in (("float32", None), ("bfloat16", "q8_0")):
        tag = f"kv={kv_quant or 'f32'}"
        run = serve_args(dtype=dtype, kv_quant=kv_quant)
        t0 = time.perf_counter()
        eng, done = serve.serve_requests(run, cfg, qparams)
        print(f"[{tag}] serve: {time.perf_counter() - t0:.1f} s wall, "
              f"compiles included")
        check_served(tag, run, cfg, done)
        ref = Engine(eng.model, qparams, max_len=run.max_len,
                     page_size=run.page_size, kernel="gather",
                     kv_quant=kv_quant)
        t0 = time.perf_counter()
        compare_steps(f"{tag} fused vs gather", eng, ref, run.slots,
                      run.seed, rtol=LOGIT_RTOL, agree_floor=AGREE_FLOOR)
        print(f"[{tag}] fused vs gather: {time.perf_counter() - t0:.1f} s, "
              f"the reference's compiles included")
    kernel_vs_twin(args, cfg)


def _common_prefix(a: list[int], b: list[int]) -> int:
    n = 0
    while n < min(len(a), len(b)) and a[n] == b[n]:
        n += 1
    return n


def four_chips() -> None:
    check(len(jax.devices()) >= 4,
          f"--four-chips needs 4 devices, found {len(jax.devices())}")
    args = serve_args(kv_quant="q8_0")
    t0 = time.perf_counter()
    cfg, qparams = serve.load_quantized(args)
    jax.block_until_ready(qparams)
    print(f"init + quantize: {time.perf_counter() - t0:.1f} s")
    engines, outs = {}, {}
    for spec in ("2x2", "none"):
        run = serve_args(kv_quant="q8_0", mesh=spec)
        t0 = time.perf_counter()
        engines[spec], done = serve.serve_requests(run, cfg, qparams)
        print(f"[mesh={spec}] serve: {time.perf_counter() - t0:.1f} s wall, "
              f"compiles included")
        outs[spec] = check_served(f"mesh={spec}", run, cfg, done)
    prefix = [_common_prefix(outs["2x2"][rid], outs["none"][rid])
              for rid in sorted(outs["none"])]
    print(f"greedy tokens shared before the first difference, 2x2 mesh vs "
          f"one chip, per request: {prefix} of {args.max_new}")
    compare_steps("mesh 2x2 vs one chip", engines["2x2"], engines["none"],
                  args.slots, args.seed, rtol=MESH_RTOL, agree_floor=1.0)
    check(outs["2x2"] == outs["none"],
          "greedy outputs differ between the 2x2 mesh and one chip")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="serve on a 2x2 mesh and compare with one chip")
    opts = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (device 0 is {dev.platform}); "
              "nothing run", file=sys.stderr)
        return 1
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache: {enable_compile_cache()}")
    try:
        four_chips() if opts.four_chips else one_chip()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
