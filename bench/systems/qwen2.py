"""The system under test for every ``qwen2`` configuration: the repo's
``Model`` and ``Engine``, with weights built on the device from the seed.

Each quantized matrix is drawn and packed in one jitted call per column
chunk (one compile per distinct chunk shape and format) with the
program's own ``core.qtensor.quantize``, in the formats the program's
policy assigns (``core.apply.format_map``).  Eager ``quantize_params`` is
not used.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchkit import weights as W
from repro.configs.base import ModelConfig
from repro.core.apply import format_map
from repro.core.formats import FLOAT_BITS
from repro.core.policy import get_policy
from repro.core.qtensor import QTensor, quantize
from repro.models import spec as mspec
from repro.models.model import Model
from repro.serving.engine import Engine
from repro.serving.sampler import SamplerConfig


def model_config(cfg: dict) -> ModelConfig:
    """The program's ``ModelConfig`` for a Qwen2-architecture file."""
    h = cfg["num_attention_heads"]
    return ModelConfig(
        name=cfg.get("name", "qwen2"), family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=h, n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // h, d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], qkv_bias=True,
        rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        norm_eps=float(cfg["rms_norm_eps"]))


@partial(jax.jit, static_argnums=(1, 2, 3))
def _packed(key, shape, std, fmt):
    return quantize(W.normal(key, shape, std), fmt).fields


def _split(path: str) -> tuple[int | None, str]:
    parts = path.split("/")
    if len(parts) == 1:
        return None, parts[0]
    return int(parts[1][1:]), parts[-1]


def build_params(cfg: dict, mcfg: ModelConfig, seed: int) -> dict:
    """The program's flat parameter dict, built on the device."""
    fmap = format_map(mcfg, get_policy(cfg["quantization"]["policy"]))
    root = W.root_key(seed)
    params = {}
    for path, s in mspec.model_specs(mcfg).items():
        layer, name = _split(path)
        key = W.tensor_key(root, layer, name)
        fmt = fmap[path]
        if len(s.shape) == 1:
            params[path] = W.vector(key, s.shape[0])
            continue
        k, n = s.shape
        std = W.matrix_std(name, k, cfg["weights"]["residual_scale_layers"])
        parts = [_packed(W.chunk_key(key, i), (k, c1 - c0), std, fmt)
                 for i, (c0, c1) in enumerate(W.column_chunks(n))]
        fields = parts[0] if len(parts) == 1 else {
            f: jnp.concatenate([p[f] for p in parts], axis=-1)
            for f in parts[0]}
        params[path] = QTensor(dict(fields), fmt, (k, n))
        del parts
    return params


def build_engine(cfg: dict, cell: dict, seed: int) -> Engine:
    """The engine a cell serves through, holding the seed's weights."""
    mcfg = model_config(cfg)
    stated = cfg["quantization"]["formats"]
    for name, fmt in formats(cfg).items():
        if stated.get(name) != fmt:
            raise ValueError(
                f"policy {cfg['quantization']['policy']} packs {name} as "
                f"{fmt}, the configuration states {stated.get(name)}")
    params = build_params(cfg, mcfg, seed)
    return Engine(Model(mcfg, dtype=jnp.bfloat16), params,
                  max_len=cell["max_len"],
                  sampler=SamplerConfig(greedy=True),
                  page_size=cell["page_size"],
                  prefill_chunk=cell["prefill_chunk"],
                  kernel="fused", kv_quant=cell["kv_quant"])


def formats(cfg: dict) -> dict[str, str]:
    """Weight name -> format, as the program's policy resolves it (the
    configuration file states the same table for the reference)."""
    mcfg = model_config(cfg)
    fmap = format_map(mcfg, get_policy(cfg["quantization"]["policy"]))
    return {_split(p)[1]: f for p, f in fmap.items() if f not in FLOAT_BITS}
