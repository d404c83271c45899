"""Operations and bytes a step needs, from the configuration's shapes.

These are the algorithm's needs for the live tokens, not what a kernel
happens to touch: a kernel that reads more (padding, a whole bucket of
pages, position pools) shows it as a lower share of its roofline.
"""

from __future__ import annotations

from typing import Iterable


def dims(cfg: dict) -> dict:
    h = cfg["num_attention_heads"]
    return {"d": cfg["hidden_size"], "f": cfg["intermediate_size"],
            "h": h, "kv": cfg["num_key_value_heads"],
            "hd": cfg["hidden_size"] // h, "layers": cfg["num_hidden_layers"],
            "vocab": cfg["vocab_size"]}


def layer_matmul_params(cfg: dict) -> int:
    m = dims(cfg)
    d, hd = m["d"], m["hd"]
    attn = d * m["h"] * hd * 2 + d * m["kv"] * hd * 2
    return attn + 3 * d * m["f"]


def token_flops(cfg: dict, context: int, head: bool) -> float:
    """FLOPs of one token through every layer, attending over ``context``
    positions (itself included), plus the LM head when ``head``."""
    m = dims(cfg)
    per_layer = 2 * layer_matmul_params(cfg) + 4 * m["h"] * m["hd"] * context
    return m["layers"] * per_layer + (2 * m["d"] * m["vocab"] if head else 0)


def kv_bytes_per_token(cfg: dict, kv_quant: str | None) -> float:
    """Bytes of one token's K and V in one layer's pools."""
    m = dims(cfg)
    width = m["kv"] * m["hd"]
    if kv_quant is None:
        return 2 * width * 2                       # bfloat16 K and V
    if kv_quant == "q8_0":
        return 2 * (width + 4 * m["kv"])           # int8 + f32 per-row scale
    if kv_quant == "q4_0":
        return 2 * (width / 2 + 4 * m["kv"])
    raise ValueError(f"unknown kv_quant {kv_quant!r}")


def paged_decode_call(cfg: dict, kv_quant: str | None,
                      lane_tokens: Iterable[int]) -> tuple[float, float]:
    """(bytes, FLOPs) one layer's decode attention needs for live lanes
    holding ``lane_tokens`` tokens each: their K/V once, the query in
    bfloat16, the float32 output."""
    m = dims(cfg)
    toks = list(lane_tokens)
    per_q = m["h"] * m["hd"]
    nbytes = (sum(toks) * kv_bytes_per_token(cfg, kv_quant)
              + len(toks) * per_q * (2 + 4))
    flops = 4.0 * per_q * sum(toks)
    return nbytes, flops
