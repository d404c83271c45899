"""Sharded serving: ``Engine(mesh=...)`` must be bitwise-identical to
single-device serving, and the weights-sharded-but-engine-unsharded
split must be structurally impossible.

The mesh tests need >= 8 local devices; run them on CPU with

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m pytest -q tests/test_sharded_serving.py

(the flag must be set before the first jax import, so it cannot live in
conftest.py — CI's ``sharded-parity`` job exports it).  On a bare
single-device run only the layout-split regression tests execute.

Two mesh shapes exercise both kernel sharding regimes of the reduced
qwen2-1.5b config (4 query heads, 2 KV heads):

  * ``4x2`` — model=2 divides both head counts: shard_map splits heads
    and the KV pools shard on the kv-head axis;
  * ``2x4`` — model=4 divides only the query heads: the kernels fall
    back to the replicated path and pools shard on the page axis.

Bitwise parity holds because weights are only *stored* sharded — every
contraction streams the full weight per device (see
``Engine._constrained``) — and the head-split attention output is
gathered before ``o_proj``, so no contraction is summed across shards
(``tests/test_tpu_compile.py`` holds the compiled TPU step to no
all-reduce; the CPU's dots happen to give the same bits either way).
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import paged_attn
from repro.launch.mesh import describe_mesh, mesh_from_spec
from repro.models import paged
from repro.parallel import sharding as shard
from repro.serving.engine import Engine, Request
from repro.serving.sampler import SamplerConfig

from test_paged_cache import _setup
from test_paged_attn_kernel import _build_pools

_GREEDY = SamplerConfig(greedy=True)

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


def _requests(cfg, n=3, seed=1, max_new=8):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=list(rng.integers(4, cfg.vocab_size, 5 + i)),
                    max_new=max_new)
            for i in range(n)]


def _serve(model, params, reqs, *, mesh=None, slots=2, **kw):
    eng = Engine(model, params, max_len=64, page_size=8, kernel="fused",
                 sampler=_GREEDY, mesh=mesh, **kw)
    done = eng.serve([Request(r.rid, list(r.prompt), r.max_new, r.priority)
                      for r in reqs], slots=slots, seed=0)
    return {r.rid: list(r.out) for r in done}, eng.last_stats


# ---------------------------------------------------------------------------
# mesh_from_spec / constructor validation (single-device safe)
# ---------------------------------------------------------------------------

def test_mesh_from_spec_none():
    assert mesh_from_spec(None) is None
    assert mesh_from_spec("none") is None


@pytest.mark.parametrize("bad", ["", "2x", "x4", "axb", "0x4", "2x4x2"])
def test_mesh_from_spec_rejects_malformed(bad):
    with pytest.raises(ValueError):
        mesh_from_spec(bad)


def test_mesh_from_spec_rejects_too_many_devices():
    # 4096 devices exist on no host this test runs on; the error must
    # mention the CPU-repro escape hatch
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        mesh_from_spec("64x64")


def test_engine_mesh_requires_paged_cache():
    _, params, model = _setup("qwen2-1.5b")
    mesh = mesh_from_spec("1x1")
    with pytest.raises(ValueError, match="page_size"):
        Engine(model, params, max_len=32, jit=False, mesh=mesh)


# ---------------------------------------------------------------------------
# the layout split itself: sharded weights + unsharded engine must raise
# ---------------------------------------------------------------------------

@needs_mesh
def test_sharded_params_without_mesh_rejected():
    """The bug this PR fixes: weights laid out across a mesh handed to
    an engine that serves single-device.  Engine(mesh=None) must refuse
    multi-device params instead of silently serving them."""
    cfg, params, model = _setup("qwen2-1.5b")
    mesh = mesh_from_spec("2x4")
    sharded = jax.device_put(
        params, shard.tree_shardings(params, cfg, mesh,
                                     plan=getattr(model, "plan", None)))
    with pytest.raises(ValueError, match="no mesh"):
        Engine(model, sharded, max_len=32, page_size=8, jit=False)
    # the same params ARE accepted when the engine owns the mesh
    eng = Engine(model, sharded, max_len=32, page_size=8, mesh=mesh)
    assert eng.mesh is mesh


@needs_mesh
def test_engine_lays_out_weights_on_its_mesh():
    cfg, params, model = _setup("qwen2-1.5b")
    mesh = mesh_from_spec("2x4")
    eng = Engine(model, params, max_len=32, page_size=8, mesh=mesh)
    devs = {d for leaf in jax.tree_util.tree_leaves(eng.params)
            for d in leaf.sharding.device_set}
    assert devs == set(mesh.devices.flat)


# ---------------------------------------------------------------------------
# bitwise token parity vs single-device serving
# ---------------------------------------------------------------------------

@needs_mesh
@pytest.mark.parametrize("spec", ["2x4", "4x2"])
@pytest.mark.parametrize("arch,kv_quant", [
    ("qwen2-1.5b", None),            # full GQA attention, f32 pools
    ("deepseek-v3-671b", None),      # MLA latents + MoE experts
    ("qwen2-1.5b", "q8_0"),          # quantized pools
    ("qwen2-1.5b", "q4_0"),          # nibble-packed pools
    ("deepseek-v3-671b", "dq"),      # per-layer bitwidth, latents q8
], ids=["attn-f32", "mla-f32", "attn-q8", "attn-q4", "mla-dq"])
def test_mesh_serve_bitwise_parity(arch, kv_quant, spec):
    cfg, params, model = _setup(arch)
    reqs = _requests(cfg)
    ref, _ = _serve(model, params, reqs, kv_quant=kv_quant)
    got, stats = _serve(model, params, reqs, kv_quant=kv_quant,
                        mesh=mesh_from_spec(spec))
    assert got == ref, {k: (ref[k], got[k]) for k in ref if got[k] != ref[k]}
    assert stats.mesh == spec
    assert stats.pages_leaked == 0


# ---------------------------------------------------------------------------
# pool invariants + preemption/swap round-trip under a sharded pool
# ---------------------------------------------------------------------------

@needs_mesh
def test_mesh_pool_invariants():
    cfg, params, model = _setup("qwen2-1.5b")
    mesh = mesh_from_spec("2x4")
    got, stats = _serve(model, params, _requests(cfg, n=4), mesh=mesh,
                        slots=2)
    assert len(got) == 4
    # the pool is padded to a multiple of the mesh so the page axis
    # shards evenly, and every page allocated during the run came back
    assert stats.num_pages % mesh.size == 0
    assert stats.pages_leaked == 0
    assert 0 < stats.peak_pages <= stats.num_pages


@needs_mesh
def test_mesh_preempt_swap_roundtrip_bitwise():
    """Preemption under a *sharded* pool: swap-out gathers pool rows off
    the mesh, swap-in scatters them back, and the outputs stay bitwise
    equal to an unsharded, unpreempted serve."""
    cfg, params, model = _setup("qwen2-1.5b")
    reqs = _requests(cfg, n=5, max_new=24)
    ref, ref_stats = _serve(model, params, reqs, slots=2)
    assert ref_stats.preemptions == 0

    # 8 total pages (already a multiple of mesh.size, so the mesh pads
    # nothing): 6 usable, vs a 5-page single-request worst case — three
    # lanes cannot coexist, forcing swap-out/swap-in round-trips
    got, stats = _serve(model, params, reqs, slots=3,
                        mesh=mesh_from_spec("2x4"), scheduler="preempt",
                        num_pages=paged.RESERVED_PAGES + 6,
                        swap_budget_bytes=1 << 30)
    assert got == ref, {k: (ref[k], got[k]) for k in ref if got[k] != ref[k]}
    assert stats.preemptions > 0
    assert stats.swap_out_bytes == stats.swap_in_bytes > 0
    assert stats.pages_leaked == 0


# ---------------------------------------------------------------------------
# swap-budget default (satellite: bounded by default, warns on restart)
# ---------------------------------------------------------------------------

def test_swap_budget_defaults_to_ram_fraction():
    _, params, model = _setup("qwen2-1.5b")
    eng = Engine(model, params, max_len=32, page_size=4, jit=False,
                 scheduler="preempt")
    assert eng.swap_budget_bytes is not None and eng.swap_budget_bytes > 0
    assert eng._swap_budget_defaulted
    # explicit values (including 0) are never overridden
    eng0 = Engine(model, params, max_len=32, page_size=4, jit=False,
                  scheduler="preempt", swap_budget_bytes=0)
    assert eng0.swap_budget_bytes == 0 and not eng0._swap_budget_defaulted
    # non-preempt schedulers keep no budget at all
    engr = Engine(model, params, max_len=32, page_size=4, jit=False)
    assert engr.swap_budget_bytes is None


def test_swap_budget_default_warns_once_on_restart(monkeypatch):
    """When the *default* cap forces evict-to-restart the engine warns
    exactly once; an explicit cap stays silent (the caller asked)."""
    from repro.serving import engine as engine_mod
    cfg, params, model = _setup("qwen2-1.5b")
    reqs = _requests(cfg, n=4, max_new=24)
    kw = dict(slots=3, scheduler="preempt",
              num_pages=paged.RESERVED_PAGES + 6)

    monkeypatch.setattr(engine_mod, "_default_swap_budget", lambda: 0)
    with pytest.warns(UserWarning, match="DEFAULT swap budget") as rec:
        got, stats = _serve(model, params, reqs, **kw)
    assert stats.swap_restarts > 0 and stats.swap_out_bytes == 0
    assert len([w for w in rec
                if "DEFAULT swap budget" in str(w.message)]) == 1

    with warnings.catch_warnings():
        warnings.simplefilter("error")  # explicit budget: no warning
        got2, stats2 = _serve(model, params, reqs, swap_budget_bytes=0,
                              **kw)
    assert stats2.swap_restarts > 0
    assert got2 == got


# ---------------------------------------------------------------------------
# kernel-level shard_map parity (pallas interpret path, head-split specs)
# ---------------------------------------------------------------------------

@needs_mesh
@pytest.mark.parametrize("spec", ["4x2", "2x4"],
                         ids=["head-split", "replicated-fallback"])
def test_kernel_shard_map_matches_unsharded(spec):
    """The fused Pallas kernel under shard_map: the replicated fallback
    (model axis does not divide the KV heads) is the identical
    computation on every device — bitwise.  The head-split path runs the
    kernel on a different head-block shape per shard, which reassociates
    the softmax reductions, so it is float-noise close (the per-shard
    ``run`` closure derives every shape constant from per-shard
    operands, keeping the result head-correct)."""
    rng = np.random.default_rng(0)
    b, h, hkv, d, dv, n_lp, page_size = 3, 4, 2, 16, 8, 4, 8
    pos = rng.integers(0, n_lp * page_size - 1, size=b).astype(np.int32)
    k_pool, v_pool, pos_pool, bt = _build_pools(
        rng, b, n_lp, page_size, hkv, d, dv, pos)
    q = rng.normal(size=(b, h, d)).astype(np.float32)
    args = (jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(pos_pool), jnp.asarray(bt), jnp.asarray(pos))
    ref = np.asarray(paged_attn.paged_attn_decode(
        *args, impl="pallas", interpret=True))
    mesh = mesh_from_spec(spec)
    got = np.asarray(paged_attn.paged_attn_decode(
        *args, impl="pallas", interpret=True, mesh=mesh))
    if mesh.shape["model"] > 1 and 2 % mesh.shape["model"] == 0:
        np.testing.assert_allclose(got, ref, atol=2e-6, rtol=2e-6)
    else:
        np.testing.assert_array_equal(got, ref)


@needs_mesh
@pytest.mark.parametrize("spec", ["4x2", "2x4"],
                         ids=["head-split", "replicated-fallback"])
def test_prefill_kernels_shard_map_match_unsharded(spec):
    """The q8_0 chunked-prefill kernels (GQA and MLA) under shard_map —
    the path Engine(mesh=...) takes with quantized pools, where a Mosaic
    call outside shard_map cannot be partitioned — against the same
    kernels on one device."""
    rng = np.random.default_rng(1)
    b, c, page_size, n_lp, hkv, rep, d, r, dr = 2, 6, 4, 3, 2, 2, 16, 16, 8
    h = hkv * rep
    n_pages = paged.RESERVED_PAGES + b * n_lp
    bt = paged.RESERVED_PAGES + np.arange(b * n_lp, dtype=np.int32).reshape(
        b, n_lp)
    pos_pool = np.full((n_pages, page_size), -1, np.int32)
    pos_pool[paged.RESERVED_PAGES:] = np.tile(
        np.arange(n_lp * page_size).reshape(n_lp, page_size), (b, 1))
    qpos = jnp.asarray(np.stack([np.arange(c), np.arange(2, 2 + c)]),
                       jnp.int32)

    def pool(*row):
        return paged.quantize_rows(jnp.asarray(
            rng.normal(size=(n_pages, page_size, *row)), jnp.float32), "q8_0")

    kq, kd = pool(hkv, d)
    vq, vd = pool(hkv, d)
    cq, cd = pool(r)
    rq, rd = pool(dr)
    q = jnp.asarray(rng.normal(size=(b, c, h, d)), jnp.float32)
    q_eff = jnp.asarray(rng.normal(size=(b, c, h, r)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(b, c, h, dr)), jnp.float32)
    mesh = mesh_from_spec(spec)
    for m in (None, mesh):
        got = (np.asarray(paged_attn.paged_attn_prefill_quant(
                   q, kq, kd, vq, vd, jnp.asarray(pos_pool), jnp.asarray(bt),
                   qpos, impl="pallas", interpret=True, mesh=m)),
               np.asarray(paged_attn.paged_mla_prefill_quant(
                   q_eff, q_rope, cq, cd, rq, rd, jnp.asarray(bt), qpos,
                   scale=0.3, impl="pallas", interpret=True, mesh=m)))
        if m is None:
            ref = got
    for g, want in zip(got, ref):
        np.testing.assert_allclose(g, want, atol=2e-6, rtol=2e-6)


@needs_mesh
def test_describe_mesh_roundtrip():
    mesh = mesh_from_spec("2x4")
    assert describe_mesh(mesh) == "2x4"
