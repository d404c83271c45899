"""Fused paged-attention decode kernels vs the gather reference.

Two layers of parity (on CPU the Pallas kernels run ``interpret=True``,
kernels/common.py):

  * kernel-level — :func:`repro.kernels.paged_attn.paged_attn_decode` /
    ``paged_mla_decode`` against a dense numpy oracle on hand-built page
    pools (partial last pages, odd page sizes, sliding windows incl. ring
    wraparound, NULL-page tails, ``active_pages`` bounds), for BOTH
    implementations of the algorithm: the Pallas kernel (interpret mode)
    and its bounded-gather XLA twin;
  * model-level — ``Model.decode_step_paged(kernel="fused")`` against
    ``kernel="gather"`` (itself bitwise-identical to the dense layout, see
    tests/test_paged_cache.py) across the three attention families — full
    GQA, local ring, MLA latents — within 1e-5 relative in f32, including
    ``live=False`` lanes whose cache writes must land identically, plus a
    Pallas-forced (``REPRO_PAGED_IMPL=pallas``) pass per family.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypo_compat import given, settings, st

from repro.configs import CONFIGS
from repro.kernels import paged_attn
from repro.models import paged
from repro.models.model import Model
from repro.models.spec import init_params

from test_paged_cache import _Tables, _setup

TOL = 1e-5

# the three fused-kernel families (window override as in test_paged_cache)
ARCHS = {
    "qwen2-1.5b": None,        # full GQA
    "gemma2-9b": 8,            # local ring + softcap (tiny window => wrap)
    "deepseek-v3-671b": None,  # MLA latents
}


# ---------------------------------------------------------------------------
# kernel-level parity vs a dense numpy oracle
# ---------------------------------------------------------------------------

def _build_pools(rng, b, n_lp, page_size, hkv, d, dv, pos):
    """Page pools + block tables with live entries up to ``pos`` per lane
    and NULL-page tails (partial last pages arise whenever
    ``pos+1 % page_size != 0``)."""
    n_pages = paged.RESERVED_PAGES + b * n_lp
    k_pool = rng.normal(size=(n_pages, page_size, hkv, d)).astype(np.float32)
    v_pool = rng.normal(size=(n_pages, page_size, hkv, dv)).astype(np.float32)
    pos_pool = np.full((n_pages, page_size), -1, np.int32)
    bt = np.full((b, n_lp), paged.NULL_PAGE, np.int32)
    nxt = paged.RESERVED_PAGES
    for i in range(b):
        for lp in range(pos[i] // page_size + 1):
            bt[i, lp] = nxt
            for o in range(page_size):
                idx = lp * page_size + o
                if idx <= pos[i]:
                    pos_pool[nxt, o] = idx
            nxt += 1
    # NULL page must read as unwritten
    k_pool[paged.NULL_PAGE] = 0.0
    v_pool[paged.NULL_PAGE] = 0.0
    return k_pool, v_pool, pos_pool, bt


def _dense_oracle(q, k_pool, v_pool, pos_pool, bt, pos, window, softcap):
    b, h, d = q.shape
    hkv, dv = k_pool.shape[2], v_pool.shape[3]
    rep = h // hkv
    n_lp, p = bt.shape[1], k_pool.shape[1]
    out = np.zeros((b, h, dv), np.float32)
    for i in range(b):
        ks = k_pool[bt[i]].reshape(n_lp * p, hkv, d)
        vs = v_pool[bt[i]].reshape(n_lp * p, hkv, dv)
        ps = pos_pool[bt[i]].reshape(n_lp * p)
        valid = (ps >= 0) & (ps <= pos[i])
        if window:
            valid &= ps > pos[i] - window
        for hh in range(h):
            s = (q[i, hh] @ ks[:, hh // rep].T) * d ** -0.5
            if softcap:
                s = softcap * np.tanh(s / softcap)
            s = np.where(valid, s, -np.inf)
            w = np.exp(s - s.max())
            w /= w.sum()
            out[i, hh] = w @ vs[:, hh // rep]
    return out


@given(st.integers(3, 9), st.integers(0, 1), st.integers(0, 1),
       st.sampled_from(["pallas", "xla"]), st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_kernel_matches_dense_oracle(page_size, use_window, use_softcap,
                                     impl, seed):
    """Odd page sizes, partial last pages, windows and softcaps: both
    implementations of the fused GQA decode must match a dense softmax
    oracle."""
    rng = np.random.default_rng(seed)
    b, h, hkv, d, dv, n_lp = 3, 4, 2, 16, 8, 4
    pos = rng.integers(0, n_lp * page_size - 1, size=b).astype(np.int32)
    window = 7 if use_window else 0
    softcap = 20.0 if use_softcap else 0.0
    k_pool, v_pool, pos_pool, bt = _build_pools(
        rng, b, n_lp, page_size, hkv, d, dv, pos)
    q = rng.normal(size=(b, h, d)).astype(np.float32)
    got = np.asarray(paged_attn.paged_attn_decode(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(pos_pool), jnp.asarray(bt), jnp.asarray(pos),
        window=window, softcap=softcap, impl=impl))
    ref = _dense_oracle(q, k_pool, v_pool, pos_pool, bt, pos, window,
                        softcap)
    assert np.max(np.abs(got - ref)) < TOL


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_kernel_active_pages_bound(impl):
    """Bounding the page loop to the live horizon must not change results,
    and the bound genuinely skips trailing NULL pages."""
    rng = np.random.default_rng(0)
    b, h, hkv, d, dv, page_size, n_lp = 2, 4, 2, 16, 8, 4, 8
    pos = np.array([5, 9], np.int32)               # live pages: 2 and 3
    k_pool, v_pool, pos_pool, bt = _build_pools(
        rng, b, n_lp, page_size, hkv, d, dv, pos)
    args = (jnp.asarray(k_pool), jnp.asarray(v_pool), jnp.asarray(pos_pool),
            jnp.asarray(bt), jnp.asarray(pos))
    q = jnp.asarray(rng.normal(size=(b, h, d)).astype(np.float32))
    full = np.asarray(paged_attn.paged_attn_decode(q, *args, impl=impl))
    for ap in (3, 4, 8):
        bound = np.asarray(paged_attn.paged_attn_decode(
            q, *args, active_pages=ap, impl=impl))
        assert np.max(np.abs(full - bound)) < TOL, ap
    # an insufficient bound must actually truncate (proves pages beyond
    # the bound are never read)
    trunc = np.asarray(paged_attn.paged_attn_decode(q, *args,
                                                    active_pages=1,
                                                    impl=impl))
    assert np.max(np.abs(full[1] - trunc[1])) > TOL


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_kernel_lane_pages_bound(impl):
    """Per-lane page bounds (``lane_pages``): clamping each lane's page
    loop to its OWN live pages must not change results even when another
    lane in the batch is 8x longer — and an under-bound must truncate
    only the lane it under-bounds (proves the clamp is per-lane, not a
    batch-wide minimum)."""
    rng = np.random.default_rng(2)
    b, h, hkv, d, dv, page_size, n_lp = 2, 4, 2, 16, 8, 4, 8
    pos = np.array([2, 30], np.int32)              # live pages: 1 vs 8
    k_pool, v_pool, pos_pool, bt = _build_pools(
        rng, b, n_lp, page_size, hkv, d, dv, pos)
    args = (jnp.asarray(k_pool), jnp.asarray(v_pool), jnp.asarray(pos_pool),
            jnp.asarray(bt), jnp.asarray(pos))
    q = jnp.asarray(rng.normal(size=(b, h, d)).astype(np.float32))
    full = np.asarray(paged_attn.paged_attn_decode(q, *args, impl=impl))
    lp = jnp.asarray([1, 8], jnp.int32)
    bounded = np.asarray(paged_attn.paged_attn_decode(
        q, *args, lane_pages=lp, impl=impl))
    assert np.max(np.abs(full - bounded)) < TOL
    # under-bounding the long lane truncates it; the short lane is intact
    trunc = np.asarray(paged_attn.paged_attn_decode(
        q, *args, lane_pages=jnp.asarray([1, 2], jnp.int32), impl=impl))
    assert np.max(np.abs(full[0] - trunc[0])) < TOL
    assert np.max(np.abs(full[1] - trunc[1])) > TOL
    # q8 variant honors the same bound
    kq, kd = paged_attn.quantize_kv_page_pool(jnp.asarray(k_pool))
    vq, vd = paged_attn.quantize_kv_page_pool(jnp.asarray(v_pool))
    fq = np.asarray(paged_attn.paged_attn_decode_q8(
        q, kq, kd, vq, vd, jnp.asarray(pos_pool), jnp.asarray(bt),
        jnp.asarray(pos), impl=impl))
    bq = np.asarray(paged_attn.paged_attn_decode_q8(
        q, kq, kd, vq, vd, jnp.asarray(pos_pool), jnp.asarray(bt),
        jnp.asarray(pos), lane_pages=lp, impl=impl))
    assert np.max(np.abs(fq - bq)) < TOL


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_mla_lane_pages_bound(impl):
    """MLA variant of the per-lane bound: clamped grid steps revisit the
    lane's last page, whose entries the positional mask already
    excludes, so bounded results are unchanged."""
    rng = np.random.default_rng(4)
    b, h, r, dr, page_size, n_lp = 2, 4, 12, 6, 4, 8
    pos = np.array([1, 27], np.int32)
    n_pages = paged.RESERVED_PAGES + b * n_lp
    ckv = rng.normal(size=(n_pages, page_size, r)).astype(np.float32)
    krope = rng.normal(size=(n_pages, page_size, dr)).astype(np.float32)
    bt = np.full((b, n_lp), paged.NULL_PAGE, np.int32)
    nxt = paged.RESERVED_PAGES
    for i in range(b):
        for lp_ in range(pos[i] // page_size + 1):
            bt[i, lp_] = nxt
            nxt += 1
    qe = rng.normal(size=(b, h, r)).astype(np.float32)
    qr = rng.normal(size=(b, h, dr)).astype(np.float32)
    base = (jnp.asarray(qe), jnp.asarray(qr), jnp.asarray(ckv),
            jnp.asarray(krope), jnp.asarray(bt), jnp.asarray(pos))
    full = np.asarray(paged_attn.paged_mla_decode(*base, scale=0.2,
                                                  impl=impl))
    bounded = np.asarray(paged_attn.paged_mla_decode(
        *base, scale=0.2, lane_pages=jnp.asarray([1, 7], jnp.int32),
        impl=impl))
    assert np.max(np.abs(full - bounded)) < TOL


def test_block_fetch_plan():
    """Replays the GQA decode kernel's fetch plan over its (slot, block)
    grid with the helpers the kernel runs (``_block_pages``,
    ``_next_block``): every lane copies exactly its own live pages, each
    once and in order, and none of its NULL tail; a block is copied only
    if it is live, and each live block's copies are started by the live
    step before it in grid order (the next lane's first block included),
    so only the call's first block is started on its own step."""
    plans = ((4, [1, 3, 4, 5, 12, 9]), (1, [1, 2, 3]),
             (16, [1, 15, 16, 17, 40]), (8, [8, 8]))
    for ppb, lane_pages in plans:
        b, nj = len(lane_pages), max(lane_pages)
        nb = -(-nj // ppb)
        bt = np.full((b, nj), paged.NULL_PAGE, np.int32)
        nxt = paged.RESERVED_PAGES
        for i, n in enumerate(lane_pages):
            bt[i, :n] = np.arange(nxt, nxt + n)
            nxt += n
        copied = [[] for _ in range(b)]

        def start(lane, blk):
            n = int(paged_attn._block_pages(lane_pages[lane], blk, ppb))
            assert n > 0, (lane, blk)              # only live blocks
            copied[lane] += [int(bt[lane, blk * ppb + pg])
                             for pg in range(n)]
            return lane, blk

        started, live_steps = [start(0, 0)], []
        for i in range(b):
            for blk in range(nb):
                if int(paged_attn._block_pages(lane_pages[i], blk,
                                               ppb)) == 0:
                    continue                        # dead step: nothing
                assert started[-1] == (i, blk)      # prefetched earlier
                live_steps.append((i, blk))
                ni, nblk = (int(x) for x in paged_attn._next_block(
                    i, blk, lane_pages[i], ppb))
                if ni < b:
                    started.append(start(ni, nblk))
        assert started == live_steps
        assert len(live_steps) == sum(-(-n // ppb) for n in lane_pages)
        for i, n in enumerate(lane_pages):
            assert copied[i] == list(bt[i, :n]), (ppb, i)
            assert paged.NULL_PAGE not in copied[i]


def _lane_pools(rng, pos, page_size, n_lp, hkv, d, dv, ring):
    """Pools for lanes at positions ``pos``, each lane owning ``n_lp``
    physical pages.  Full tables store position = logical index; a
    ``ring`` table of ``n_lp`` pages holds at slot s the latest position
    t <= pos with t = s mod its length.  Returns the pools, the block
    table (NULL past each lane's live pages), the lane page counts, and
    a poisoned table whose entries past them point at the lane's other
    pages, filled with NaN keys and values and in-range positions."""
    b, span = len(pos), n_lp * page_size
    n_pages = paged.RESERVED_PAGES + b * n_lp
    k_pool = rng.normal(size=(n_pages, page_size, hkv, d)).astype(np.float32)
    v_pool = rng.normal(size=(n_pages, page_size, hkv, dv)).astype(np.float32)
    pos_pool = np.full((n_pages, page_size), -1, np.int32)
    k_pool[paged.NULL_PAGE] = v_pool[paged.NULL_PAGE] = 0.0
    bt = np.full((b, n_lp), paged.NULL_PAGE, np.int32)
    poisoned = np.zeros_like(bt)
    lane_pages = []
    for i, p in enumerate(pos):
        n = paged.pages_for((min(p + 1, span) if ring else p + 1), page_size)
        lane_pages.append(n)
        own = paged.RESERVED_PAGES + i * n_lp + np.arange(n_lp)
        poisoned[i], bt[i, :n] = own, own[:n]
        for s in range(n * page_size):
            t = p - (p - s) % span if ring else s
            if 0 <= t <= p:
                pos_pool[own[s // page_size], s % page_size] = t
        k_pool[own[n:]] = v_pool[own[n:]] = np.nan
        pos_pool[own[n:]] = 0
    return (k_pool, v_pool, pos_pool, bt, np.asarray(lane_pages, np.int32),
            poisoned)


# (page_size, nj, lane positions, window, softcap, storage, lane bound
# given, block tokens): with 16 block tokens a block is 4 pages of 4
BLOCK_CASES = {
    # lane pages 1, ppb - 1, ppb, ppb + 1 and the whole bucket
    "edges": (4, 12, [2, 9, 15, 17, 47], 0, 0.0, None, True, 16),
    # no lane bound: every lane walks the bucket, NULL tails included
    "null_tails": (4, 12, [2, 17, 47], 0, 0.0, None, False, 16),
    # a bucket smaller than a block: one block of the bucket's pages
    "small_bucket": (4, 2, [0, 5, 7], 0, 0.0, None, True, 16),
    # wrapped ring tables with a window, and softcap
    "ring": (4, 6, [2, 13, 30, 47], 7, 20.0, None, True, 16),
    "q8_0": (4, 12, [0, 13, 16, 47], 0, 0.0, "q8_0", True, 16),
    "q4_0": (4, 12, [0, 13, 16, 47], 9, 15.0, "q4_0", True, 16),
    # the serving pools' dtype, bf16 queries and pages
    "bf16": (4, 12, [3, 16, 33], 0, 0.0, "bf16", True, 16),
    # the module's own block size: 64 pages of 4
    "block_256": (4, 72, [0, 251, 255, 259, 287], 0, 0.0, None, True,
                  None),
}


@pytest.mark.parametrize("case", list(BLOCK_CASES))
def test_block_loop_matches_oracle(case, monkeypatch):
    """The GQA decode kernel's block loop (Pallas, interpret mode) and its
    XLA twin against the dense oracle, over lanes that end in every
    position of a block.  The kernel runs on the poisoned table: a copy
    of any page past a lane's live pages would put NaN in its output."""
    (page_size, nj, pos, window, softcap, storage, bounded,
     block_tokens) = BLOCK_CASES[case]
    if block_tokens:
        monkeypatch.setattr(paged_attn, "_BLOCK_TOKENS", block_tokens)
    rng = np.random.default_rng(list(BLOCK_CASES).index(case))
    h, hkv, d = 4, 2, 16
    dv = 8 if storage is None else d
    pos = np.asarray(pos, np.int32)
    k_pool, v_pool, pos_pool, bt, lane_pages, poisoned = _lane_pools(
        rng, pos, page_size, nj, hkv, d, dv, ring=bool(window))
    q = rng.normal(size=(len(pos), h, d)).astype(np.float32)
    kw = dict(window=window, softcap=softcap,
              lane_pages=jnp.asarray(lane_pages) if bounded else None,
              active_pages=nj)
    if storage in ("q8_0", "q4_0"):
        # int8 values cannot hold NaN: poison the pages' row scales
        extra = np.setdiff1d(poisoned, bt)
        leaves = []
        for x in (k_pool, v_pool):
            xq, xd = paged.quantize_rows(jnp.asarray(np.nan_to_num(x)),
                                         storage)
            leaves.append((xq, xd.at[extra].set(jnp.nan)))
        (kq, kd), (vq, vd) = leaves
        k_pool, v_pool = (np.asarray(paged_attn._dequant(x, s, storage))
                          for x, s in leaves)

        def attend(table, impl):
            return paged_attn.paged_attn_decode_quant(
                jnp.asarray(q), kq, kd, vq, vd, jnp.asarray(pos_pool),
                jnp.asarray(table), jnp.asarray(pos), mode=storage,
                impl=impl, **kw)
    else:
        dt = jnp.bfloat16 if storage == "bf16" else jnp.float32
        q, k_pool, v_pool = (np.asarray(jnp.asarray(x, dt), np.float32)
                             for x in (q, k_pool, v_pool))

        def attend(table, impl):
            return paged_attn.paged_attn_decode(
                jnp.asarray(q, dt), jnp.asarray(k_pool, dt),
                jnp.asarray(v_pool, dt), jnp.asarray(pos_pool),
                jnp.asarray(table), jnp.asarray(pos), impl=impl, **kw)
    ref = _dense_oracle(q, k_pool, v_pool, pos_pool, bt, pos, window,
                        softcap)
    twin = np.asarray(attend(bt, "xla"))
    got = np.asarray(attend(poisoned if bounded else bt, "pallas"))
    assert np.max(np.abs(twin - ref)) < TOL
    assert np.max(np.abs(got - ref)) < TOL


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_q8_kernel_matches_dequantised_oracle(impl):
    """The q8_0 variant (stretch: quantized KV pages) must attend exactly
    as the f32 kernel over the *dequantised* pools — dequantisation happens
    inside the page loop, never as a dense pass."""
    rng = np.random.default_rng(11)
    b, h, hkv, d, dv, page_size, n_lp = 2, 4, 2, 16, 16, 5, 3
    pos = np.array([7, 12], np.int32)
    k_pool, v_pool, pos_pool, bt = _build_pools(
        rng, b, n_lp, page_size, hkv, d, dv, pos)
    kq, kd = paged_attn.quantize_kv_page_pool(jnp.asarray(k_pool))
    vq, vd = paged_attn.quantize_kv_page_pool(jnp.asarray(v_pool))
    q = rng.normal(size=(b, h, d)).astype(np.float32)
    got = np.asarray(paged_attn.paged_attn_decode_q8(
        jnp.asarray(q), kq, kd, vq, vd, jnp.asarray(pos_pool),
        jnp.asarray(bt), jnp.asarray(pos), window=6, softcap=15.0,
        impl=impl))
    kf = np.asarray(kq, np.float32) * np.asarray(kd)[..., None]
    vf = np.asarray(vq, np.float32) * np.asarray(vd)[..., None]
    ref = _dense_oracle(q, kf, vf, pos_pool, bt, pos, 6, 15.0)
    assert np.max(np.abs(got - ref)) < TOL
    # and the quantisation itself is q8_0-accurate
    assert np.max(np.abs(kf - k_pool)) < np.max(np.abs(k_pool)) / 100


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_mla_kernel_matches_dense_oracle(impl):
    rng = np.random.default_rng(3)
    b, h, r, dr, page_size, n_lp = 3, 4, 12, 6, 5, 4
    pos = np.array([0, 7, 19], np.int32)           # empty-ish / partial / full
    n_pages = paged.RESERVED_PAGES + b * n_lp
    ckv = rng.normal(size=(n_pages, page_size, r)).astype(np.float32)
    krope = rng.normal(size=(n_pages, page_size, dr)).astype(np.float32)
    bt = np.full((b, n_lp), paged.NULL_PAGE, np.int32)
    nxt = paged.RESERVED_PAGES
    for i in range(b):
        for lp in range(pos[i] // page_size + 1):
            bt[i, lp] = nxt
            nxt += 1
    qe = rng.normal(size=(b, h, r)).astype(np.float32)
    qr = rng.normal(size=(b, h, dr)).astype(np.float32)
    scale = 0.21
    got = np.asarray(paged_attn.paged_mla_decode(
        jnp.asarray(qe), jnp.asarray(qr), jnp.asarray(ckv),
        jnp.asarray(krope), jnp.asarray(bt), jnp.asarray(pos), scale=scale,
        impl=impl))
    for i in range(b):
        cs = ckv[bt[i]].reshape(-1, r)
        ks = krope[bt[i]].reshape(-1, dr)
        valid = np.arange(cs.shape[0]) <= pos[i]
        for hh in range(h):
            s = (qe[i, hh] @ cs.T + qr[i, hh] @ ks.T) * scale
            s = np.where(valid, s, -np.inf)
            w = np.exp(s - s.max())
            w /= w.sum()
            assert np.max(np.abs(got[i, hh] - w @ cs)) < TOL, (i, hh)


@pytest.mark.parametrize("max_rows", [None, 16])
@pytest.mark.parametrize("mode", ["q8_0", "q4_0"])
def test_prefill_quant_kernels_match_xla_twin(mode, max_rows, monkeypatch):
    """The fused chunked-prefill kernels (Pallas, interpret mode) against
    their bounded-gather XLA twins, GQA and MLA, with padded chunk rows
    (qpos = -1) and NULL-page tails.  ``max_rows=16`` splits each chunk's
    query rows into two blocks per slot (the path whole-prompt chunks
    take at real widths)."""
    if max_rows:
        monkeypatch.setattr(paged_attn, "_MAX_ROWS", max_rows)
        jax.clear_caches()
    rng = np.random.default_rng(11)
    b, c, page_size, n_lp = 2, 8, 4, 4
    hkv, rep, d, r, dr = 2, 2, 8, 16, 8
    h = hkv * rep
    n_pages = paged.RESERVED_PAGES + b * n_lp
    bt = np.full((b, n_lp), paged.NULL_PAGE, np.int32)
    pos_pool = np.full((n_pages, page_size), -1, np.int32)
    horizon = (11, 6)                     # keys written up to these positions
    nxt = paged.RESERVED_PAGES
    for i in range(b):
        for j in range(paged.pages_for(horizon[i] + 1, page_size)):
            bt[i, j] = nxt
            pos_pool[nxt] = np.arange(j * page_size, (j + 1) * page_size)
            nxt += 1
    qpos = np.stack([np.arange(4, 4 + c),                # lane 0: 4..11
                     np.r_[np.arange(0, 7), -1]])        # lane 1: padded
    qpos = jnp.asarray(qpos, jnp.int32)

    def pools(*row_shape, mode=mode):
        x = rng.normal(size=(n_pages, page_size, *row_shape))
        return paged.quantize_rows(jnp.asarray(x, jnp.float32), mode)

    kq, kd = pools(hkv, d)
    vq, vd = pools(hkv, d)
    q = jnp.asarray(rng.normal(size=(b, c, h, d)), jnp.float32)
    gqa = {impl: np.asarray(paged_attn.paged_attn_prefill_quant(
        q, kq, kd, vq, vd, jnp.asarray(pos_pool), jnp.asarray(bt), qpos,
        mode=mode, impl=impl)) for impl in ("pallas", "xla")}
    np.testing.assert_allclose(gqa["pallas"], gqa["xla"], rtol=TOL,
                               atol=TOL)
    assert np.all(gqa["pallas"][1, -1] == 0.0)           # padded row

    cq, cd = pools(r, mode="q8_0")       # latents stay q8_0 under "dq"
    rq, rd = pools(dr)
    q_eff = jnp.asarray(rng.normal(size=(b, c, h, r)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(b, c, h, dr)), jnp.float32)
    mla = {impl: np.asarray(paged_attn.paged_mla_prefill_quant(
        q_eff, q_rope, cq, cd, rq, rd, jnp.asarray(bt), qpos, scale=0.3,
        latent_mode="q8_0", rope_mode=mode, impl=impl))
        for impl in ("pallas", "xla")}
    np.testing.assert_allclose(mla["pallas"], mla["xla"], rtol=TOL,
                               atol=TOL)
    assert np.all(mla["pallas"][1, -1] == 0.0)


@pytest.mark.parametrize("chunk", [1, 3, 5])
def test_prefill_kernel_chunk_split_bitwise(chunk):
    """The Pallas prefill kernel enumerates pages in block-table order
    whatever the chunk split, so each query row's output is bit for bit
    the same whether it is attended in a split chunk or in the whole
    prompt (the XLA twin matches only to float reassociation)."""
    rng = np.random.default_rng(5)
    b, c, page_size, hkv, rep, d = 2, 12, 4, 2, 2, 16
    n_pages = paged.RESERVED_PAGES + b * 3
    kq, kd = paged.quantize_rows(jnp.asarray(
        rng.normal(size=(n_pages, page_size, hkv, d)), jnp.float32), "q4_0")
    vq, vd = paged.quantize_rows(jnp.asarray(
        rng.normal(size=(n_pages, page_size, hkv, d)), jnp.float32), "q4_0")
    bt = paged.RESERVED_PAGES + np.arange(b * 3, dtype=np.int32).reshape(b, 3)
    pos_pool = np.full((n_pages, page_size), -1, np.int32)
    pos_pool[paged.RESERVED_PAGES:] = np.tile(
        np.arange(3 * page_size).reshape(3, page_size), (b, 1))
    q = jnp.asarray(rng.normal(size=(b, c, hkv * rep, d)), jnp.float32)
    qpos = jnp.tile(jnp.arange(c, dtype=jnp.int32)[None], (b, 1))

    def attend(lo, hi):
        return np.asarray(paged_attn.paged_attn_prefill_quant(
            q[:, lo:hi], kq, kd, vq, vd, jnp.asarray(pos_pool),
            jnp.asarray(bt), qpos[:, lo:hi], mode="q4_0", impl="pallas"))

    whole = attend(0, c)
    for lo in range(0, c, chunk):
        hi = min(c, lo + chunk)
        assert np.array_equal(attend(lo, hi), whole[:, lo:hi]), (chunk, lo)


# ---------------------------------------------------------------------------
# model-level parity: fused vs gather through Model.decode_step_paged
# ---------------------------------------------------------------------------

def _relerr(a, b):
    return float(jnp.max(jnp.abs(a - b))) / (float(jnp.max(jnp.abs(a)))
                                             + 1e-9)


def _run_fused_parity(arch, page_size, plens, steps, max_len=32,
                      live_holdout=None, check_active=True):
    """Stream prompts into two identical paged caches, then decode with the
    gather reference and the fused kernels; logits of live lanes must agree
    within TOL and the page pools (outside the reserved write-sink pages)
    must stay identical."""
    cfg, params, model = _setup(arch)
    rng = np.random.default_rng(hash((arch, page_size, *plens)) % 2**31)
    b = len(plens)
    tbl = _Tables(cfg, b, max_len, page_size)
    cache_g = model.init_paged_cache(tbl.pool.num_pages, page_size, b,
                                     dtype=jnp.float32)
    cache_f = cache_g
    pos = [0] * b
    chunk = 4
    lg = None
    while any(pos[s] < plens[s] for s in range(b)):
        toks = np.zeros((b, chunk), np.int32)
        start = np.zeros(b, np.int32)
        clen = np.zeros(b, np.int32)
        for s in range(b):
            n = min(chunk, plens[s] - pos[s])
            if n <= 0:
                continue
            toks[s, :n] = rng.integers(4, cfg.vocab_size, n)
            start[s], clen[s] = pos[s], n
            tbl.ensure(s, pos[s], pos[s] + n)
            pos[s] += n
        lg, cache_g = model.prefill_chunk(
            params, cache_g, jnp.asarray(toks), jnp.asarray(start),
            jnp.asarray(clen), max_len=max_len, block_tables=tbl.asdict(),
            page_size=page_size)
        cache_f = cache_g

    tok = jnp.argmax(lg, -1).astype(jnp.int32)
    pos_arr = jnp.asarray(plens, jnp.int32)
    live = (None if live_holdout is None
            else jnp.asarray([s != live_holdout for s in range(b)]))

    def held_pages():
        ids = set(tbl.full[live_holdout]) | set(tbl.ring[live_holdout])
        return sorted(i for i in ids if i >= paged.RESERVED_PAGES)

    for i in range(steps):
        for s in range(b):
            tbl.ensure(s, plens[s] + i, plens[s] + i + 1)
        if live_holdout is not None:
            hp = held_pages()
            snap = {key: np.asarray(cache_f[key])[hp] for key in cache_f}
        lg, cache_g = model.decode_step_paged(
            params, cache_g, tok, pos_arr, tbl.asdict(),
            page_size=page_size, max_len=max_len, live=live,
            kernel="gather")
        lf, cache_f = model.decode_step_paged(
            params, cache_f, tok, pos_arr, tbl.asdict(),
            page_size=page_size, max_len=max_len, live=live,
            kernel="fused")
        for s in range(b):
            if live is not None and not bool(live[s]):
                continue
            assert _relerr(lg[s], lf[s]) < TOL, (arch, i, s)
        if check_active:
            horizon = int(np.max(np.asarray(pos_arr))) + 1
            active = (paged.pages_for(horizon, page_size) if tbl.n_full
                      else 0,
                      paged.pages_for(min(horizon, tbl.ring_len), page_size)
                      if tbl.n_ring else 0)
            la, _ = model.decode_step_paged(
                params, cache_g, tok, pos_arr, tbl.asdict(),
                page_size=page_size, max_len=max_len, live=live,
                kernel="fused", active_pages=active)
            for s in range(b):
                if live is None or bool(live[s]):
                    assert _relerr(lg[s], la[s]) < TOL, (arch, i, s,
                                                         "active")
        # pools march in lockstep outside the reserved write sink (floats
        # to tolerance: per-layer deltas differ by ~1e-7 between the two
        # implementations, so later layers' cache *writes* inherit that)
        for key in cache_g:
            g, f = np.asarray(cache_g[key]), np.asarray(cache_f[key])
            if g.dtype.kind == "i":
                assert np.array_equal(g[paged.RESERVED_PAGES:],
                                      f[paged.RESERVED_PAGES:]), (arch, key)
            else:
                assert np.allclose(g[paged.RESERVED_PAGES:],
                                   f[paged.RESERVED_PAGES:],
                                   atol=1e-4), (arch, key)
        # a non-live lane's pages must come through the fused step untouched
        if live_holdout is not None:
            for key in cache_f:
                after = np.asarray(cache_f[key])[hp]
                assert np.array_equal(after, snap[key]), (arch, key, i)
        # advance both from the gather logits so states stay comparable
        tok = jnp.argmax(lg, -1).astype(jnp.int32)
        pos_arr = pos_arr + 1


@pytest.mark.parametrize("arch", list(ARCHS))
def test_fused_matches_gather(arch):
    _run_fused_parity(arch, page_size=4, plens=(11, 6), steps=3)


@pytest.mark.parametrize("arch", list(ARCHS))
def test_fused_matches_gather_odd_pages(arch):
    """Odd page sizes leave partial last pages almost every step."""
    _run_fused_parity(arch, page_size=5, plens=(9, 13), steps=3)
    _run_fused_parity(arch, page_size=7, plens=(7, 8), steps=2)


def test_fused_matches_gather_ring_wraparound():
    """Prompts past the shrunk window force ring wraparound mid-decode."""
    _run_fused_parity("gemma2-9b", page_size=3, plens=(21, 13), steps=4)


@pytest.mark.parametrize("arch", list(ARCHS))
def test_fused_live_false_lanes(arch):
    """A non-live lane's throwaway row must leave the shared pools exactly
    as the gather path does (writes routed to the garbage page), and live
    lanes must still match."""
    _run_fused_parity(arch, page_size=4, plens=(10, 5), steps=3,
                      live_holdout=1, check_active=False)


@pytest.mark.parametrize("arch", list(ARCHS))
def test_fused_pallas_impl_through_model(arch, monkeypatch):
    """REPRO_PAGED_IMPL=pallas routes the model-level fused path through
    the real Pallas kernels (interpret mode on CPU) — the deployment
    configuration, kept small because interpret execution is slow."""
    monkeypatch.setenv(paged_attn.PAGED_IMPL_ENV, "pallas")
    _run_fused_parity(arch, page_size=4, plens=(6, 3), steps=2,
                      check_active=False)


def test_env_selects_gather_reference(monkeypatch):
    """REPRO_PAGED_KERNEL=gather routes the default through the reference
    path (bitwise-equal logits to an explicit kernel="gather" call)."""
    from repro.models import attention
    monkeypatch.setenv(attention.PAGED_KERNEL_ENV, "gather")
    assert attention.default_paged_kernel() == "gather"
    cfg, params, model = _setup("qwen2-1.5b")
    page_size, max_len, b = 4, 16, 2
    tbl = _Tables(cfg, b, max_len, page_size)
    cache = model.init_paged_cache(tbl.pool.num_pages, page_size, b,
                                   dtype=jnp.float32)
    for s in range(b):
        tbl.ensure(s, 0, 3)
    toks = jnp.asarray(np.full((b, 3), 7, np.int32))
    zeros = jnp.zeros(b, jnp.int32)
    _, cache = model.prefill_chunk(
        params, cache, toks, zeros, jnp.asarray([3, 3], jnp.int32),
        max_len=max_len, block_tables=tbl.asdict(), page_size=page_size)
    pos_arr = jnp.asarray([3, 3], jnp.int32)
    tok = jnp.asarray([5, 6], jnp.int32)
    for s in range(b):
        tbl.ensure(s, 3, 4)
    l_env, _ = model.decode_step_paged(
        params, cache, tok, pos_arr, tbl.asdict(), page_size=page_size,
        max_len=max_len)
    l_ref, _ = model.decode_step_paged(
        params, cache, tok, pos_arr, tbl.asdict(), page_size=page_size,
        max_len=max_len, kernel="gather")
    assert np.array_equal(np.asarray(l_env), np.asarray(l_ref))
