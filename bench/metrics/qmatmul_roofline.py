"""Kernels: the quantized weight matmuls' share of their roofline over
the traced decode steps.

Least time of one step's weight products is the larger of the packed
bytes of every matrix the step reads over HBM bandwidth, and 2 x matrix
parameters x live lanes over the bf16 peak; at these shapes the bytes
bound it.  The bytes follow the configuration's stated formats at the
public GGUF block sizes; with tied embeddings the head is
``token_embd``, in its format.  The share is the summed least time over
the device time of the operations under the ``qmatmul`` scope (the head
included) inside the window's runs of ``jit_engine_decode``, paired in
order with the traced decode calls for their live lanes.
"""

from benchkit import costs, runner, spans

# bytes per block, weights per block (GGUF / ggml block layouts)
GGUF_BLOCK = {"q4_k": (144, 256), "q6_k": (210, 256), "q8_0": (34, 32)}


def matrices(cfg: dict) -> list[tuple[str, int]]:
    """(format key, weights) of every matrix one decode step reads."""
    m = costs.dims(cfg)
    d, q, kv, f = m["d"], m["h"] * m["hd"], m["kv"] * m["hd"], m["f"]
    layer = [("q_proj", d * q), ("k_proj", d * kv), ("v_proj", d * kv),
             ("o_proj", q * d), ("gate", d * f), ("up", d * f),
             ("down", f * d)]
    head = "token_embd" if cfg["tie_word_embeddings"] else "output"
    return layer * m["layers"] + [(head, m["vocab"] * d)]


def weight_bytes(cfg: dict) -> float:
    fmts = cfg["quantization"]["formats"]
    total = 0.0
    for key, n in matrices(cfg):
        nbytes, per = GGUF_BLOCK[fmts[key]]
        total += n * nbytes / per
    return total


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    sp = spans.read(runner.TRACE_DIR)
    steps = sp.runs(spans.DECODE)
    calls = [c for c in run.traced_calls if c.kind == "decode"]
    n = min(len(steps), len(calls))
    if n == 0:
        return None
    spent = sum(e.dur for e, path in sp.scoped_ops(steps[:n])
                if "qmatmul" in path.split("/"))
    if spent <= 0:
        return None
    cfg, pk = run.cell.cfg, run.peaks
    nbytes = weight_bytes(cfg)
    params = sum(w for _, w in matrices(cfg))
    least = sum(max(nbytes / pk.hbm_bw,
                    2.0 * params * len(c.lane_tokens) / pk.bf16_flops)
                for c in calls[:n])
    return 100.0 * least / spent
