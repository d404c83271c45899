"""The plain float32 reference against the program's own forward at a tiny
Qwen2 size (float32 activations, the same seeded k-quant weights), with
the layer stack unrolled and scanned, and the blockwise attention against
one block."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from benchkit import runner

SEED = 2**32 + 77
T = 40


@pytest.fixture(scope="module")
def ref():
    return runner.load_module(runner.BENCH / "reference" / "qwen2.py",
                              "bench_reference_qwen2")


@pytest.fixture(scope="module")
def program_logits():
    from repro.core.policy import get_policy
    from repro.models import stacking
    from repro.models.model import Model
    system = runner.load_module(runner.BENCH / "systems" / "qwen2.py",
                                "bench_system_qwen2")
    mcfg = system.model_config(tiny.CONFIG)
    params = system.build_params(tiny.CONFIG, mcfg, SEED)
    toks = np.random.default_rng(0).integers(0, 512, T).astype(np.int32)
    batch = {"tokens": jnp.asarray(toks)[None]}
    out = {"tokens": toks}
    out["unrolled"], _ = Model(mcfg, dtype=jnp.float32).forward(params, batch)
    sp = stacking.plan(mcfg, get_policy("DQ3_K_M"))
    out["scan"], _ = Model(mcfg, scan=True, plan=sp, dtype=jnp.float32
                           ).forward(stacking.stack_tree(params, sp), batch)
    return out


def _evaluate(ref, toks, served):
    rows = np.arange(T)
    return ref.evaluate(tiny.CONFIG, SEED, [toks], [rows], [served])


@pytest.mark.parametrize("mode", ["unrolled", "scan"])
def test_reference_matches_program_forward(ref, program_logits, mode):
    lg = np.asarray(program_logits[mode][0], np.float32)      # (T, V)
    served = lg.argmax(-1).astype(np.int32)
    r = _evaluate(ref, program_logits["tokens"], served)
    scale = np.abs(lg).max()
    np.testing.assert_allclose(r["best"], lg.max(-1), atol=1e-4 * scale)
    np.testing.assert_allclose(r["served"], lg.max(-1), atol=1e-4 * scale)
    assert np.max(r["best"] - r["served"]) < 1e-4 * scale


def test_blockwise_attention_equals_one_block(ref, program_logits,
                                              monkeypatch):
    toks = program_logits["tokens"]
    served = np.zeros(T, np.int32)
    whole = _evaluate(ref, toks, served)
    monkeypatch.setattr(ref, "Q_BLOCK", 16)
    ref._layer.clear_cache()
    blocks = _evaluate(ref, toks, served)
    ref._layer.clear_cache()
    np.testing.assert_allclose(blocks["best"], whole["best"], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_array_equal(blocks["argmax"], whole["argmax"])


def test_reference_quantizer_matches_program_formats():
    """The reference's own k-quant round trip gives the values the
    program's packed formats dequantize to."""
    import jax
    from repro.core.qtensor import quantize
    kq = runner.load_module(runner.BENCH / "reference" / "kquant.py",
                            "bench_reference_kquant")
    w = jax.random.normal(jax.random.PRNGKey(1), (512, 96)) * 0.05
    for fmt in kq.FORMATS:
        got = kq.quant_dequant(w, fmt)
        want = quantize(w, fmt).dequantize(jnp.float32)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=fmt)
