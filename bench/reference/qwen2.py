"""Plain Qwen2 forward in float32 (arXiv:2407.10671): the reference that
decides ``correct`` for every ``qwen2`` configuration.

Pre-norm decoder: RMSNorm -> GQA attention with q/k/v biases and rotary
positions (theta from the config, rotate-half) -> residual; RMSNorm ->
SwiGLU MLP -> residual; final RMSNorm; LM head (the embedding when tied).
Every matrix product runs in float32 at HIGHEST precision on weights that
``kquant`` round-trips from the seeded draws of ``benchkit.weights``.
Norm weights are drawn as offsets ``s`` and used as ``1 + s``, which is the
published ``x * w`` with ``w = 1 + s``.

It works one layer at a time over every sequence, so only one layer's
weights exist in float32 at once, and attention runs in blocks of query
rows.  ``mode="control"`` is the same computation one precision below what
the configuration states: every activation entering a matrix product in
float8 (e4m3, a scale per row), and the K/V rows one step below the
cell's pools (float8 for bfloat16 pools, int4 for q8_0 pools).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchkit import weights as W
from reference import kquant

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
HEAD_ROWS = 256


def _dims(cfg: dict):
    h = cfg["num_attention_heads"]
    d = cfg["hidden_size"]
    return dict(d=d, f=cfg["intermediate_size"], h=h,
                kv=cfg["num_key_value_heads"], hd=d // h,
                layers=cfg["num_hidden_layers"], vocab=cfg["vocab_size"],
                vocab_stored=-(-cfg["vocab_size"] // 256) * 256,
                eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]))


@partial(jax.jit, static_argnums=(1, 2, 3))
def _qdq(key, shape, std, fmt):
    return kquant.quant_dequant(W.normal(key, shape, std), fmt)


def matrix(cfg: dict, root, layer, name: str, shape) -> jax.Array:
    """The float32 values of one quantized matrix, drawn in the same
    column chunks as the program's."""
    fmt = cfg["quantization"]["formats"][name]
    key = W.tensor_key(root, layer, name)
    std = W.matrix_std(name, shape[0], cfg["weights"]["residual_scale_layers"])
    parts = [_qdq(W.chunk_key(key, i), (shape[0], c1 - c0), std, fmt)
             for i, (c0, c1) in enumerate(W.column_chunks(shape[1]))]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def vector(root, layer, name: str, n: int) -> jax.Array:
    v = W.vector(W.tensor_key(root, layer, name), n).astype(jnp.float32)
    return 1.0 + v if name.endswith("norm") else v


def layer_weights(cfg: dict, root, layer: int) -> dict:
    m = _dims(cfg)
    d, f, qw, kvw = m["d"], m["f"], m["h"] * m["hd"], m["kv"] * m["hd"]
    shapes = {"q_proj": (d, qw), "k_proj": (d, kvw), "v_proj": (d, kvw),
              "o_proj": (qw, d), "gate": (d, f), "up": (d, f),
              "down": (f, d)}
    w = {k: matrix(cfg, root, layer, k, s) for k, s in shapes.items()}
    for k, n in (("q_bias", qw), ("k_bias", kvw), ("v_bias", kvw),
                 ("attn_norm", d), ("ffn_norm", d)):
        w[k] = vector(root, layer, k, n)
    return w


# -- precision of the control --------------------------------------------

def _fp8(x):
    """float8 e4m3 with one scale per row of the last axis."""
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _int4(x):
    d = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 7.0
    d = jnp.where(d > 0, d, 1.0)
    return jnp.clip(jnp.round(x / d), -8, 7) * d


def _act(x, mode):
    return _fp8(x) if mode == "control" else x


def _kv(x, mode, pools):
    if mode != "control":
        return x
    return _int4(x) if pools == "q8_0" else _fp8(x)


def _mm(x, w, mode):
    return jnp.dot(_act(x, mode), w, precision=HI)


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    t, _, hd = x.shape
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@partial(jax.jit, static_argnames=("dims", "mode", "pools"))
def _layer(w, x, *, dims, mode, pools):
    d = dict(dims)
    t = x.shape[0]
    h, kv, hd = d["h"], d["kv"], d["hd"]
    a = _norm(x, w["attn_norm"], d["eps"])
    q = (_mm(a, w["q_proj"], mode) + w["q_bias"]).reshape(t, h, hd)
    k = (_mm(a, w["k_proj"], mode) + w["k_bias"]).reshape(t, kv, hd)
    v = (_mm(a, w["v_proj"], mode) + w["v_bias"]).reshape(t, kv, hd)
    q, k = _rope(q, d["theta"]), _rope(k, d["theta"])
    k, v = _kv(k, mode, pools), _kv(v, mode, pools)
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    q = _act(q, mode) * hd ** -0.5
    outs = []
    for b in range(0, t, Q_BLOCK):
        e = min(b + Q_BLOCK, t)
        s = jnp.einsum("qhd,khd->hqk", q[b:e], k[:e], precision=HI)
        causal = jnp.arange(e)[None, :] <= jnp.arange(b, e)[:, None]
        s = jnp.where(causal[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", _act(p, mode), v[:e],
                               precision=HI))
    o = jnp.concatenate(outs, 0).reshape(t, h * hd)
    x = x + _mm(o, w["o_proj"], mode)
    b2 = _norm(x, w["ffn_norm"], d["eps"])
    g = _mm(b2, w["gate"], mode)
    u = _mm(b2, w["up"], mode)
    return x + _mm(jax.nn.silu(g) * u, w["down"], mode)


@partial(jax.jit, static_argnames=("vocab", "mode"))
def _head_rows(hw, rows, served, other, *, vocab, mode):
    """Per row: the best logit, its token, and the logits of ``served``
    and ``other``."""
    lg = jnp.dot(_act(rows, mode), hw, precision=HI)[:, :vocab]
    pick = lambda t: jnp.take_along_axis(lg, t[:, None], 1)[:, 0]  # noqa
    return (jnp.max(lg, -1), jnp.argmax(lg, -1).astype(jnp.int32),
            pick(served), pick(other))


def _bucket(n: int) -> int:
    return -(-n // Q_BLOCK) * Q_BLOCK


def evaluate(cfg: dict, seed: int, seqs: list[np.ndarray],
             rows: list[np.ndarray], served: list[np.ndarray],
             other: list[np.ndarray] | None = None, *,
             mode: str = "reference", pools: str | None = None) -> dict:
    """Run every sequence of token ids through the model and read the head
    at ``rows`` (positions whose next token was served).  Returns per-row
    arrays, concatenated over sequences: ``best`` logit, ``argmax`` token,
    the logit of the ``served`` token and of the ``other`` token."""
    m = _dims(cfg)
    dims = tuple(sorted(m.items()))
    root = W.root_key(seed)
    emb = matrix(cfg, root, None, "token_embd", (m["d"], m["vocab_stored"]))
    xs = []
    for s in seqs:
        toks = np.zeros(_bucket(len(s)), np.int32)
        toks[:len(s)] = s
        xs.append(jnp.take(emb, jnp.asarray(toks), axis=1).T)
    if not cfg["tie_word_embeddings"]:
        del emb
    for layer in range(m["layers"]):
        w = layer_weights(cfg, root, layer)
        xs = [_layer(w, x, dims=dims, mode=mode, pools=pools) for x in xs]
        del w
    fin = vector(root, None, "output_norm", m["d"])
    hw = emb if cfg["tie_word_embeddings"] else matrix(
        cfg, root, None, "output", (m["d"], m["vocab_stored"]))
    out = {"best": [], "argmax": [], "served": [], "other": []}
    other = other if other is not None else served
    for x, r, sv, ot in zip(xs, rows, served, other):
        h = _norm(x[jnp.asarray(r)], fin, m["eps"])
        for b in range(0, len(r), HEAD_ROWS):
            n = min(HEAD_ROWS, len(r) - b)
            pad = lambda a: jnp.asarray(np.pad(a[b:b + n], (0, HEAD_ROWS - n)))  # noqa
            hr = jnp.pad(h[b:b + n], ((0, HEAD_ROWS - n), (0, 0)))
            res = _head_rows(hw, hr, pad(sv), pad(ot), vocab=m["vocab"],
                             mode=mode)
            for key, v in zip(("best", "argmax", "served", "other"), res):
                out[key].append(np.asarray(v[:n]))
    return {k: np.concatenate(v) if v else np.zeros(0)
            for k, v in out.items()}
