"""Ahead-of-time compiles of the fused paged-attention kernels for TPU v5e.

Interpret mode (the CPU test path) accepts block shapes, layouts and casts
that the chip's compiler refuses.  These tests compile each serving-path
kernel with ``interpret=False`` / ``impl="pallas"`` for one chip of a
described ``v5e:2x2`` topology at real model widths — qwen2-1.5b for GQA,
DeepSeek-V3 for MLA — and check that the compiled program holds the Pallas
kernel (``tpu_custom_call``).  The mesh cases compile the GQA kernels for
all four chips under ``Engine(mesh=...)``'s head split: a Mosaic kernel
that is not wrapped in ``shard_map`` cannot be partitioned.  Nothing runs;
no chip is needed.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and pytest-xdist workers
all import every test file.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import paged_attn as pa
from repro.models import paged

os.environ.setdefault("TPU_LOG_DIR", "disabled")

SLOTS = 4
PAGE = 16
MAX_LEN = 512
CHUNK = 32
N_LOGICAL = MAX_LEN // PAGE
NUM_PAGES = paged.RESERVED_PAGES + SLOTS * N_LOGICAL
# the benchmark's chat cell: 64 lanes, a live-horizon bucket of 64 pages
CELL = (64, 64)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_cache():
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_cache):
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh2x2(topo, no_cache):
    """The (data=2, model=2) serving mesh over the four described chips."""
    return jax.sharding.Mesh(np.array(topo.devices).reshape(2, 2),
                             ("data", "model"))


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_text(fn, *specs) -> str:
    return jax.jit(fn).lower(*specs).compile().as_text()


def _kv_leaves(sh, row_shape, mode, num_pages=NUM_PAGES):
    """Shapes of one quantized K/V-style leaf pair, or of one unquantized
    leaf (``mode`` None: f32, or "bf16" — the serving pools' model dtype)."""
    if mode in (None, "bf16"):
        dtype = jnp.bfloat16 if mode else jnp.float32
        return (_spec(sh, (num_pages, PAGE, *row_shape), dtype),)
    width = row_shape[-1]
    if mode == "q4_0":
        width = paged.q4_packed_dim(width)
    return (_spec(sh, (num_pages, PAGE, *row_shape[:-1], width), jnp.int8),
            _spec(sh, (num_pages, PAGE, *row_shape[:-1]), jnp.float32))


def _tables(sh, slots=SLOTS, n_logical=N_LOGICAL):
    return (_spec(sh, (slots, n_logical), jnp.int32),
            _spec(sh, (slots,), jnp.int32))


@pytest.mark.parametrize("mode, geometry", [
    pytest.param(None, None, id="None"),
    pytest.param("bf16", None, id="bf16"),
    pytest.param("q8_0", None, id="q8_0"),
    pytest.param("q4_0", None, id="q4_0"),
    pytest.param("bf16", CELL, id="bf16-cell"),
    pytest.param("q8_0", CELL, id="q8_0-cell"),
    pytest.param("q4_0", CELL, id="q4_0-cell"),
])
def test_gqa_decode_compiles(one_chip, mode, geometry):
    """qwen2-1.5b's decode attention, with per-lane page bounds as the
    engine passes them; ``geometry`` (lanes, bucket pages) the chat cell's,
    where a block is 16 pages and a lane runs up to 4 of them."""
    cfg = get_config("qwen2-1.5b")
    h, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    slots, n_logical = geometry or (SLOTS, N_LOGICAL)
    num_pages = paged.RESERVED_PAGES + slots * n_logical
    # bf16 queries score bf16 pages as stored (the cell's activations);
    # f32 queries promote them
    q = _spec(one_chip, (slots, h, d),
              jnp.bfloat16 if geometry and mode == "bf16" else jnp.float32)
    k = _kv_leaves(one_chip, (hkv, d), mode, num_pages)
    v = _kv_leaves(one_chip, (hkv, d), mode, num_pages)
    pos_pool = _spec(one_chip, (num_pages, PAGE), jnp.int32)
    bt, pos = _tables(one_chip, slots, n_logical)
    lanes = pos

    if mode in (None, "bf16"):
        def fn(q, k, v, pp, bt, pos, lanes):
            return pa.paged_attn_decode(
                q, k, v, pp, bt, pos, active_pages=n_logical,
                lane_pages=lanes, impl="pallas", interpret=False)
    else:
        def fn(q, kq, kd, vq, vd, pp, bt, pos, lanes):
            return pa.paged_attn_decode_quant(
                q, kq, kd, vq, vd, pp, bt, pos, mode=mode,
                active_pages=n_logical, lane_pages=lanes, impl="pallas",
                interpret=False)
    text = _compile_text(fn, q, *k, *v, pos_pool, bt, pos, lanes)
    assert "tpu_custom_call" in text
    storage = {None: "float32", "bf16": "bfloat16"}.get(mode, mode)
    assert f"%paged_attn_decode_full_{storage}" in text


def _mla_dims():
    cfg = get_config("deepseek-v3-671b")
    return cfg.n_heads, cfg.kv_lora_rank, cfg.qk_rope_head_dim


@pytest.mark.parametrize("modes", [None, ("q8_0", "q8_0"), ("q8_0", "q4_0"),
                                   ("q4_0", "q4_0")])
def test_mla_decode_compiles(one_chip, modes):
    h, r, dr = _mla_dims()
    q_eff = _spec(one_chip, (SLOTS, h, r), jnp.float32)
    q_rope = _spec(one_chip, (SLOTS, h, dr), jnp.float32)
    bt, pos = _tables(one_chip)
    if modes is None:
        def fn(qe, qr, ckv, kr, bt, pos):
            return pa.paged_mla_decode(
                qe, qr, ckv, kr, bt, pos, scale=0.1, active_pages=N_LOGICAL,
                impl="pallas", interpret=False)
        args = (q_eff, q_rope, *_kv_leaves(one_chip, (r,), None),
                *_kv_leaves(one_chip, (dr,), None), bt, pos)
    else:
        def fn(qe, qr, cq, cd, kq, kd, bt, pos):
            return pa.paged_mla_decode_quant(
                qe, qr, cq, cd, kq, kd, bt, pos, scale=0.1,
                latent_mode=modes[0], rope_mode=modes[1],
                active_pages=N_LOGICAL, impl="pallas", interpret=False)
        args = (q_eff, q_rope, *_kv_leaves(one_chip, (r,), modes[0]),
                *_kv_leaves(one_chip, (dr,), modes[1]), bt, pos)
    text = _compile_text(fn, *args)
    assert "tpu_custom_call" in text
    storage = "_".join(modes) if modes else "float32"
    assert f"%paged_mla_decode_{storage}" in text


@pytest.mark.parametrize("chunk", [CHUNK, MAX_LEN])
@pytest.mark.parametrize("mode", ["q8_0", "q4_0"])
def test_gqa_prefill_compiles(one_chip, mode, chunk):
    """C=32, and a whole-prompt chunk (the engine's default chunk is
    max_len), whose rows the kernel splits into blocks."""
    cfg = get_config("qwen2-1.5b")
    h, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _spec(one_chip, (SLOTS, chunk, h, d), jnp.float32)
    k = _kv_leaves(one_chip, (hkv, d), mode)
    v = _kv_leaves(one_chip, (hkv, d), mode)
    pos_pool = _spec(one_chip, (NUM_PAGES, PAGE), jnp.int32)
    bt, _ = _tables(one_chip)
    qpos = _spec(one_chip, (SLOTS, chunk), jnp.int32)

    def fn(q, kq, kd, vq, vd, pp, bt, qpos):
        return pa.paged_attn_prefill_quant(
            q, kq, kd, vq, vd, pp, bt, qpos, mode=mode,
            active_pages=N_LOGICAL, impl="pallas", interpret=False)
    text = _compile_text(fn, q, *k, *v, pos_pool, bt, qpos)
    assert "tpu_custom_call" in text
    assert f"%paged_attn_prefill_full_{mode}" in text


@pytest.mark.parametrize("modes", [("q8_0", "q8_0"), ("q8_0", "q4_0")])
def test_mla_prefill_compiles(one_chip, modes):
    h, r, dr = _mla_dims()
    q_eff = _spec(one_chip, (SLOTS, CHUNK, h, r), jnp.float32)
    q_rope = _spec(one_chip, (SLOTS, CHUNK, h, dr), jnp.float32)
    bt, _ = _tables(one_chip)
    qpos = _spec(one_chip, (SLOTS, CHUNK), jnp.int32)

    def fn(qe, qr, cq, cd, kq, kd, bt, qpos):
        return pa.paged_mla_prefill_quant(
            qe, qr, cq, cd, kq, kd, bt, qpos, scale=0.1,
            latent_mode=modes[0], rope_mode=modes[1],
            active_pages=N_LOGICAL, impl="pallas", interpret=False)
    text = _compile_text(
        fn, q_eff, q_rope, *_kv_leaves(one_chip, (r,), modes[0]),
        *_kv_leaves(one_chip, (dr,), modes[1]), bt, qpos)
    assert "tpu_custom_call" in text
    assert f"%paged_mla_prefill_{'_'.join(modes)}" in text


@pytest.mark.parametrize("step, geometry", [
    pytest.param("decode", None, id="decode"),
    pytest.param("prefill", None, id="prefill"),
    pytest.param("decode", CELL, id="decode-cell"),
])
def test_gqa_q8_compiles_on_mesh(mesh2x2, step, geometry):
    """q8_0 pools with their kv-head axis on ``model`` (2 kv heads over a
    model axis of 2), queries and tables replicated, as Engine(mesh=2x2)
    lays them out; ``geometry`` as in :func:`test_gqa_decode_compiles`."""
    cfg = get_config("qwen2-1.5b")
    h, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    slots, n_logical = geometry or (SLOTS, N_LOGICAL)
    num_pages = paged.RESERVED_PAGES + slots * n_logical
    PS = jax.sharding.PartitionSpec
    rep = jax.sharding.NamedSharding(mesh2x2, PS())
    head4 = jax.sharding.NamedSharding(mesh2x2, PS(None, None, "model", None))
    head3 = jax.sharding.NamedSharding(mesh2x2, PS(None, None, "model"))
    kq, kd = (_spec(head4, (num_pages, PAGE, hkv, d), jnp.int8),
              _spec(head3, (num_pages, PAGE, hkv), jnp.float32))
    pos_pool = _spec(rep, (num_pages, PAGE), jnp.int32)
    bt, pos = _tables(rep, slots, n_logical)
    if step == "decode":
        q = _spec(rep, (slots, h, d), jnp.float32)

        def fn(q, kq, kd, vq, vd, pp, bt, pos):
            return pa.paged_attn_decode_quant(
                q, kq, kd, vq, vd, pp, bt, pos, mode="q8_0",
                active_pages=n_logical, lane_pages=pos, impl="pallas",
                interpret=False, mesh=mesh2x2)
    else:
        q = _spec(rep, (SLOTS, CHUNK, h, d), jnp.float32)
        pos = _spec(rep, (SLOTS, CHUNK), jnp.int32)

        def fn(q, kq, kd, vq, vd, pp, bt, pos):
            return pa.paged_attn_prefill_quant(
                q, kq, kd, vq, vd, pp, bt, pos, mode="q8_0",
                active_pages=N_LOGICAL, impl="pallas", interpret=False,
                mesh=mesh2x2)
    assert "tpu_custom_call" in _compile_text(fn, q, kq, kd, kq, kd,
                                              pos_pool, bt, pos)


@pytest.fixture(scope="module")
def qwen_one_layer():
    """A one-layer qwen2-1.5b at its published widths (vocabulary cut to
    2048) with DQ3_K_M weights, as abstract params: the QTensor layout is
    what decides how the partitioner splits the weight contractions."""
    import dataclasses

    from repro.core import get_policy, quantize_params
    from repro.models import spec as mspec
    cfg = dataclasses.replace(get_config("qwen2-1.5b"), n_layers=1,
                              vocab_size=2048)
    qparams = quantize_params(cfg, mspec.init_params(cfg, 0),
                              get_policy("DQ3_K_M"))
    return cfg, jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), qparams)


@pytest.mark.parametrize("kernel", ["fused", "gather"])
@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_mesh_step_keeps_contractions_whole(mesh2x2, qwen_one_layer,
                                            monkeypatch, step, kernel):
    """``Engine(mesh=2x2)``'s own decode and prefill steps with q8_0 pools,
    compiled for the four chips.  Attention runs head-split, but no weight
    contraction may be split into per-device partial sums: the
    partitioner all-reduces those in bf16, which adds the halves of
    ``o_proj``'s sum in another order and precision than one chip does,
    and the mesh's logits drift from the one-chip logits."""
    from repro.models.model import Model
    from repro.serving.engine import Engine
    monkeypatch.setattr(pa, "_interpret_default", lambda: False)
    monkeypatch.setenv(pa.PAGED_IMPL_ENV, "pallas")
    cfg, params = qwen_one_layer
    eng = Engine(Model(cfg), params, max_len=MAX_LEN, page_size=PAGE,
                 prefill_chunk=CHUNK, kernel=kernel, kv_quant="q8_0",
                 mesh=mesh2x2)
    compiled = (eng.compile_decode_step(SLOTS) if step == "decode"
                else eng.compile_prefill_step(SLOTS))
    text = compiled.as_text()
    assert "all-reduce" not in text
    if kernel == "fused":
        assert "tpu_custom_call" in text
