"""Plain float32 reference of the served language models, and its
low-precision control.

Independent of the program: it imports nothing of ``repro`` and takes
nothing the program made. It draws the weights again from the seed
(``weights.for_reference``), then runs one sequence at a time, layer by
layer, in float32 at
HIGHEST matmul precision: embedding, RMSNorm, GQA attention with
half-split RoPE (and QK-norm where the configuration has it), the gated
MLP or the top-k mixture of experts with the configured per-example
capacity over the prompt, and the head.

The control is the same computation with every weight matmul done in a
precision below bfloat16, the step a later change would be tempted to
take: int8 (symmetric, weights per output channel, activations per
token, int32 accumulation) or fp8 (e4m3, scaled the same way, float32
accumulation). It must read as not correct.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
Q_CHUNK = 1024      # attention is computed in blocks of query rows


# --------------------------------------------------------------------------
# arithmetic
# --------------------------------------------------------------------------

def _quant(x, axis, low: str):
    """Symmetric scaling along ``axis`` into int8 or fp8 (e4m3):
    (values, scale)."""
    top = {"int8": 127.0, "fp8": 448.0}[low]
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30) / top
    if low == "int8":
        return jnp.clip(jnp.round(x / s), -127, 127).astype(jnp.int8), s
    return (x / s).astype(jnp.float8_e4m3fn), s


def _dot(spec, xq, wq, low: str):
    """Products of quantized operands: int32 accumulation for int8,
    float32 for fp8 (each product of two e4m3 values is exact in it)."""
    if low == "int8":
        return jnp.einsum(spec, xq, wq,
                          preferred_element_type=jnp.int32).astype(jnp.float32)
    return jnp.einsum(spec, xq.astype(jnp.float32), wq.astype(jnp.float32),
                      precision=HIGHEST)


def _mm(x, w, low):
    """x (..., k) @ w (k, n): float32 at HIGHEST, or ``low`` precision
    per token and per output channel."""
    if low is None:
        return jnp.matmul(x, w, precision=HIGHEST)
    xq, xs = _quant(x, -1, low)
    wq, ws = _quant(w, 0, low)
    return _dot("...k,kn->...n", xq, wq, low) * xs * ws


def _expert_mm(x, w, low):
    """x (T, k) through every expert's w (E, k, n) -> (T, E, n)."""
    if low is None:
        return jnp.einsum("tk,ekn->ten", x, w, precision=HIGHEST)
    xq, xs = _quant(x, -1, low)
    wq, ws = _quant(w, 1, low)
    return _dot("tk,ekn->ten", xq, wq, low) * xs[:, None] * ws[None, :, 0]


def _expert_out(h, w, low):
    """h (T, E, f) through each expert's own w (E, f, d) -> (T, E, d)."""
    if low is None:
        return jnp.einsum("tef,efd->ted", h, w, precision=HIGHEST)
    hq, hs = _quant(h, -1, low)
    wq, ws = _quant(w, 1, low)
    return _dot("tef,efd->ted", hq, wq, low) * hs * ws[None, :, 0]


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta):
    """x (T, H, D), half-split rotation at positions 0..T-1."""
    T, _, D = x.shape
    half = D // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, scale):
    """Causal GQA: q (T, H, D), k and v (T, KV, D); blocks of query rows."""
    T, H, D = q.shape
    g = H // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    n = -(-T // Q_CHUNK)
    qs = jnp.pad(q, ((0, n * Q_CHUNK - T), (0, 0), (0, 0))).reshape(
        n, Q_CHUNK, H, D)

    def block(args):
        i, qc = args
        s = jnp.einsum("qhd,khd->hqk", qc * scale, k, precision=HIGHEST)
        qpos = i * Q_CHUNK + jnp.arange(Q_CHUNK)[:, None]
        s = jnp.where(jnp.arange(T)[None] <= qpos, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, (jnp.arange(n), qs))
    return out.reshape(n * Q_CHUNK, H, D)[:T]


def _moe(c, h, lw, prompt_len, capacity, low):
    """Top-k mixture of experts. Over the prompt's positions each expert
    takes at most ``capacity`` of the (token, choice) pairs in token
    order and drops the rest; later positions are never dropped."""
    e, k = c["num_local_experts"], c["num_experts_per_tok"]
    logits = _mm(h, lw["mlp.router"], low)
    probs = jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    gate = top / jnp.sum(top, axis=-1, keepdims=True)
    T = h.shape[0]
    onehot = jax.nn.one_hot(idx.reshape(-1), e, dtype=jnp.int32)      # (T*k, E)
    in_prompt = (jnp.arange(T * k) // k < prompt_len)[:, None]
    rank = jnp.cumsum(onehot * in_prompt, axis=0) - 1
    pos = jnp.sum(rank * onehot, axis=-1).reshape(T, k)
    keep = (jnp.arange(T)[:, None] >= prompt_len) | (pos < capacity)
    weight = jnp.zeros((T, e)).at[jnp.arange(T)[:, None], idx].add(
        gate * keep)
    hg = _expert_mm(h, lw["mlp.wg"], low)
    hi = _expert_mm(h, lw["mlp.wi"], low)
    out = _expert_out(jax.nn.silu(hg) * hi, lw["mlp.wo"], low)
    return jnp.einsum("te,ted->td", weight, out, precision=HIGHEST)


@functools.partial(jax.jit, static_argnums=(0, 5))
def _layer(cfg_items, x, lw, prompt_len, capacity, low):
    c = dict(cfg_items)
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    h_, kv_, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    T = x.shape[0]
    lw = {n: w.astype(jnp.float32) for n, w in lw.items()}
    h = _rms(x, eps)
    q = _mm(h, lw["mix.wq"], low).reshape(T, h_, hd)
    k = _mm(h, lw["mix.wk"], low).reshape(T, kv_, hd)
    v = _mm(h, lw["mix.wv"], low).reshape(T, kv_, hd)
    if c.get("qk_layernorm"):
        q, k = _rms(q, eps), _rms(k, eps)
    q, k = _rope(q, theta), _rope(k, theta)
    scale = c.get("attention_multiplier", hd ** -0.5)
    o = _attention(q, k, v, scale).reshape(T, h_ * hd)
    res = c.get("residual_multiplier", 1.0)
    x = x + res * _mm(o, lw["mix.wo"], low)
    h = _rms(x, eps)
    if c.get("num_local_experts"):
        y = _moe(c, h, lw, prompt_len, capacity, low)
    else:
        y = _mm(jax.nn.silu(_mm(h, lw["mlp.wg"], low)) * _mm(h, lw["mlp.wi"], low),
                lw["mlp.wo"], low)
    return x + res * y


@functools.partial(jax.jit, static_argnums=(0, 4))
def _head(cfg_items, x, rows, w, low):
    c = dict(cfg_items)
    h = _rms(x[rows], c["rms_norm_eps"])
    w = w.astype(jnp.float32)
    if c["tie_word_embeddings"]:
        w = w.T
    return _mm(h, w, low) / c.get("logits_scaling", 1.0)


@functools.partial(jax.jit, static_argnums=(0,))
def _embed(cfg_items, tok, ids):
    return tok[ids].astype(jnp.float32) * dict(cfg_items).get(
        "embedding_multiplier", 1.0)


def capacity(c: dict, prompt_len: int) -> int:
    """Per-expert capacity of a prompt, as the configured router sets it."""
    e, k = c["num_local_experts"], c["num_experts_per_tok"]
    n = int(prompt_len * k / e * c["moe_capacity_factor"])
    return max(4, -(-n // 4) * 4)


def _numbers(c: dict) -> tuple:
    """The configuration's scalar entries, hashable for jit."""
    return tuple(sorted((k, v) for k, v in c.items()
                        if isinstance(v, (int, float, bool)) and not isinstance(v, str)))


def logits(c: dict, w: dict, prompt: np.ndarray, served: np.ndarray,
           rows: int, low: str | None = None) -> np.ndarray:
    """Logit rows (len(served), V) at the positions that predicted each
    served token, for the prompt followed by the served tokens; with
    ``low`` ("int8" or "fp8"), the control's. Every sequence is padded
    to ``rows`` positions, so one compiled program serves them all."""
    ids = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    T, S = len(ids), len(prompt)
    pad = rows
    if T > pad:
        raise ValueError(f"sequence of {T} positions over the {pad} padded to")
    ci = _numbers(c)
    x = _embed(ci, w["embed.tok"], jnp.asarray(np.pad(ids, (0, pad - T))))
    cap = capacity(c, S) if c.get("num_local_experts") else 0
    layer_names = [n for n in w if n.startswith(("mix.", "mlp."))]
    for l in range(c["num_hidden_layers"]):
        lw = {n: w[n][l] for n in layer_names}
        x = _layer(ci, x, lw, jnp.int32(S), jnp.int32(cap), low)
    n = T - S + 1
    at = np.minimum(np.arange(S - 1, S - 1 + -(-n // 64) * 64), pad - 1)
    head = w["embed.tok"] if c["tie_word_embeddings"] else w["embed.head"]
    return np.asarray(_head(ci, x, jnp.asarray(at), head, low))[:n]


def gaps(ref_rows: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """How far each chosen token's logit lies below its row's best."""
    return ref_rows.max(axis=-1) - ref_rows[np.arange(len(tokens)), tokens]


def worst_gap(ref_rows: np.ndarray, tokens: np.ndarray) -> float:
    """Widest gap by which a chosen token's logit lies below the row's best."""
    return float(np.max(gaps(ref_rows, tokens)))
