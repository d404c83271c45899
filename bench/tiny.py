"""A tiny Qwen2 cell for the benchmark's CPU tests: the same harness, the
same program path and the same reference, at a size a test run holds."""

from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

from benchkit import runner

CONFIG = {
    "arch": "qwen2", "hidden_size": 256, "intermediate_size": 512,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "vocab_size": 512, "rms_norm_eps": 1e-6,
    "rope_theta": 1000000.0, "tie_word_embeddings": True,
    "quantization": {"policy": "DQ3_K_M", "formats": {
        "token_embd": "q4_k", "output": "q6_k", "q_proj": "q4_k",
        "k_proj": "q6_k", "v_proj": "q6_k", "o_proj": "q4_k",
        "gate": "q4_k", "up": "q4_k", "down": "q6_k"}},
    "weights": {"residual_scale_layers": 2},
}
CELL = {"slots": 4, "max_len": 64, "page_size": 16, "kv_quant": None,
        "prefill_chunk": 16, "requests": 6,
        "trace": {"start": 0.0, "seconds": 60},
        "check": {"tokens": 40, "max_requests": 4},
        "limits": {"served_logit_gap": 0.03}}
MIX = {"prompt": {"median": 12, "sigma": 0.5, "min": 4, "max": 24},
       "output": {"median": 8, "sigma": 0.5, "min": 4, "max": 16},
       "block": 4}


def write(tmp: Path, cell: dict | None = None) -> dict:
    """Write the tiny cell's files under ``tmp``; return its spec."""
    for d in ("cells", "traffic"):
        (tmp / d).mkdir(exist_ok=True)
    (tmp / "tiny.json").write_text(json.dumps(CONFIG))
    (tmp / "cells" / "tiny.chat.json").write_text(json.dumps(cell or CELL))
    (tmp / "traffic" / "chat.json").write_text(json.dumps(MIX))
    spec = json.loads((runner.ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny", "file": str(tmp / "tiny.json")}]
    spec["workloads"] = [{"name": "tiny.chat", "config": "tiny",
                          "traffic": "chat", "chips": 1}]
    for m in spec["per_layer"]:
        m["workloads"] = ["tiny.chat"]
    return spec


def run(tmp: Path, seed: int, *, seconds: float = 120.0, trace: int = 0,
        **kw) -> dict:
    """One run of the tiny cell on whatever device JAX has."""
    spec = write(tmp, kw.pop("cell", None))
    args = argparse.Namespace(workload="tiny.chat", seed=seed,
                              seconds=seconds, trace=trace)
    return runner.run(args, t_start=time.perf_counter(), require_chip=False,
                      spec=spec, data_dir=tmp, **kw)
