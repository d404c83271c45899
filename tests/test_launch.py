"""The serving launcher's steps, the compile-cache placement, and the chip
smoke script's refusal to run without a TPU."""

from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.launch import serve
from repro.launch.compile_cache import (ENV, compile_cache_dir,
                                        enable_compile_cache)
from repro.models import paged

REPO = Path(__file__).resolve().parents[1]


def test_compile_cache_follows_env(monkeypatch, tmp_path):
    """A set JAX_COMPILATION_CACHE_DIR is the cache; nothing overrides it."""
    monkeypatch.setenv(ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache_dir() == str(tmp_path)
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_repo(monkeypatch):
    """Unset, the cache is the checkout's fixed, gitignored .jax_cache."""
    monkeypatch.delenv(ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert path == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().splitlines()


def test_serve_main_paged_q8(monkeypatch, tmp_path):
    """main = parse_args -> load_quantized -> serve_requests, end to end on
    a reduced config with fused kernels over q8_0 pages."""
    monkeypatch.setenv(ENV, str(tmp_path))
    done = serve.main(["--arch", "qwen2-1.5b", "--reduced", "--requests",
                       "3", "--slots", "2", "--max-new", "3", "--max-len",
                       "32", "--page-size", "8", "--kernel", "fused",
                       "--kv-quant", "q8_0", "--temperature", "0"])
    assert sorted(r.rid for r in done) == [0, 1, 2]
    assert all(r.status == "ok" and len(r.out) == 3 for r in done)


def test_serve_f32_pools(monkeypatch, tmp_path):
    """--dtype float32 serves f32 activations over f32 paged pools: the
    K/V bytes of a page double against the default bf16 pools (the int32
    positions stay)."""
    monkeypatch.setenv(ENV, str(tmp_path))
    argv = ["--arch", "qwen2-1.5b", "--reduced", "--requests", "2",
            "--slots", "2", "--max-new", "2", "--max-len", "32",
            "--page-size", "8", "--kernel", "fused", "--temperature", "0"]
    page_bytes = {}
    for dtype in ("bfloat16", "float32"):
        args = serve.parse_args(argv + ["--dtype", dtype])
        cfg, qparams = serve.load_quantized(args)
        eng, done = serve.serve_requests(args, cfg, qparams)
        assert eng.model.dtype == jnp.dtype(dtype)
        assert all(r.status == "ok" and len(r.out) == 2 for r in done)
        page_bytes[dtype] = eng.last_stats.page_bytes
    pos_bytes = cfg.n_layers * 8 * 4
    assert (page_bytes["float32"] - pos_bytes
            == 2 * (page_bytes["bfloat16"] - pos_bytes))


def test_engine_pool_pages_matches_serve(monkeypatch, tmp_path):
    """Engine.pool_pages is the pool serve builds: every lane's worst case
    by default, the explicit --num-pages otherwise."""
    monkeypatch.setenv(ENV, str(tmp_path))
    argv = ["--arch", "qwen2-1.5b", "--reduced", "--requests", "2",
            "--slots", "2", "--max-new", "2", "--max-len", "32",
            "--page-size", "8", "--temperature", "0"]
    for extra, want in (([], paged.RESERVED_PAGES + 2 * 4),
                        (["--num-pages", "9"], 9)):
        args = serve.parse_args(argv + extra)
        cfg, qparams = serve.load_quantized(args)
        eng, _ = serve.serve_requests(args, cfg, qparams)
        assert eng.pool_pages(2) == want == eng.last_stats.num_pages


def test_chip_smoke_refuses_without_tpu():
    """On the CPU the smoke script runs nothing and prints no result."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr
