"""Public kernel entry points.

Every op has three implementations:
  * ``ref``    — the naive pure-jnp oracle in :mod:`repro.kernels.ref`
                 (small sizes only; ground truth for tests).
  * ``xla``    — a memory-bounded pure-JAX path (chunked / associative scans)
                 that lowers on any backend. This is what the multi-pod
                 dry-run compiles on fake CPU devices.
  * ``pallas`` — the Pallas TPU kernel, compiled by Mosaic. Tests run the
                 same kernels on the CPU as ``pallas_interpret``, a choice
                 only tests make.

``set_default_impl`` switches the default globally (models call these ops
without an explicit ``impl=``).
"""
from __future__ import annotations

import contextlib
import functools
from typing import Literal

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref

Impl = Literal["ref", "xla", "pallas", "pallas_interpret"]
_DEFAULT_IMPL: Impl = "xla"
NEG_INF = _ref.NEG_INF


def set_default_impl(impl: Impl) -> None:
    global _DEFAULT_IMPL
    _DEFAULT_IMPL = impl


def get_default_impl() -> Impl:
    return _DEFAULT_IMPL


@contextlib.contextmanager
def default_impl(impl: Impl):
    prev = _DEFAULT_IMPL
    set_default_impl(impl)
    try:
        yield
    finally:
        set_default_impl(prev)


def _resolve(impl: Impl | None) -> Impl:
    return _DEFAULT_IMPL if impl is None else impl


# --------------------------------------------------------------------------
# Attention (prefill / train)
# --------------------------------------------------------------------------

def attention(
    q: jax.Array,            # (B, Sq, H, D)
    k: jax.Array,            # (B, Skv, KV, D)
    v: jax.Array,            # (B, Skv, KV, D)
    *,
    causal: bool = True,
    window: int | None = None,
    q_offset: int | jax.Array = 0,
    kv_len: jax.Array | None = None,
    scale: float | None = None,
    impl: Impl | None = None,
    q_chunk: int = 1024,
    blk_q: int | None = None,
    blk_k: int | None = None,
) -> jax.Array:
    impl = _resolve(impl)
    if impl == "ref":
        return _ref.attention(q, k, v, causal=causal, window=window,
                              q_offset=q_offset, kv_len=kv_len, scale=scale)
    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels import flash_attention as fa
        if blk_q is None or blk_k is None:
            from repro.kernels import autotune
            tuned = autotune.attention_tiling(q.shape[1], k.shape[1],
                                              q.shape[-1], str(q.dtype))
            if tuned is not None:   # else: kernel's own clamped defaults
                blk_q = blk_q if blk_q is not None else tuned["blk_q"]
                blk_k = blk_k if blk_k is not None else tuned["blk_k"]
        blks = {kk: vv for kk, vv in
                (("blk_q", blk_q), ("blk_k", blk_k)) if vv is not None}
        return fa.flash_attention(q, k, v, causal=causal, window=window,
                                  q_offset=q_offset, scale=scale, **blks,
                                  interpret=(impl == "pallas_interpret"))
    return _xla_attention(q, k, v, causal=causal, window=window,
                          q_offset=q_offset, kv_len=kv_len, scale=scale,
                          q_chunk=q_chunk)


def _xla_attention(q, k, v, *, causal, window, q_offset, kv_len, scale, q_chunk):
    """Memory-bounded attention: lax.scan over q chunks.

    Peak score buffer is (B, KV, G, q_chunk, Skv_band) instead of the full
    (Sq, Skv) square. With a sliding window, only the (q_chunk + window) key
    band is sliced per chunk, making local-attention cost O(S·W) not O(S²).
    """
    B, Sq, H, D = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    scale = (1.0 / D**0.5) if scale is None else scale

    if Sq <= q_chunk:
        return _attn_block(q, k, v, causal=causal, window=window,
                           q_offset=q_offset, kv_len=kv_len, scale=scale,
                           k_offset=0)

    n_chunks = -(-Sq // q_chunk)
    pad = n_chunks * q_chunk - Sq
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else q
    qs = qp.reshape(B, n_chunks, q_chunk, H, D).transpose(1, 0, 2, 3, 4)

    banded = window is not None and Skv > q_chunk + window
    band = None
    if banded:
        band = q_chunk + window
        band = min(band + (-band) % 128, Skv)   # pad band to lane multiple

    def chunk_fn(_, ci_q):
        ci, qc = ci_q
        off = q_offset + ci * q_chunk
        if banded:
            # keys in (off - window, off + q_chunk] → slice a static-size band
            start = jnp.clip(off - window + 1, 0, Skv - band)
            kc = jax.lax.dynamic_slice_in_dim(k, start, band, axis=1)
            vc = jax.lax.dynamic_slice_in_dim(v, start, band, axis=1)
            o = _attn_block(qc, kc, vc, causal=causal, window=window,
                            q_offset=off - start, kv_len=None, scale=scale,
                            k_offset=0)
        else:
            o = _attn_block(qc, k, v, causal=causal, window=window,
                            q_offset=off, kv_len=kv_len, scale=scale,
                            k_offset=0)
        return None, o

    _, outs = jax.lax.scan(chunk_fn, None,
                           (jnp.arange(n_chunks), qs))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, n_chunks * q_chunk, H,
                                                v.shape[-1])
    return out[:, :Sq]


def _attn_block(q, k, v, *, causal, window, q_offset, kv_len, scale, k_offset):
    """Score block in full-head (MHA-expanded) layout.

    KV heads are broadcast up to H before the score einsum so the (B, H,
    Sq, Skv) score tensor shards cleanly over the model axis even when
    KV < model-axis size (e.g. 8 KV heads on a 16-way axis — in grouped
    (KV, G) layout the leading dim can't shard and the f32 scores blow up
    per-device memory). The Pallas kernel avoids the expansion on TPU.
    """
    from repro.distributed.sharding import shard
    B, Sq, H, D = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    with jax.named_scope("flashable_attention"):
        if G > 1:
            k = jnp.repeat(k, G, axis=2)
            v = jnp.repeat(v, G, axis=2)
        k = shard(k, "batch", None, "heads", None)
        v = shard(v, "batch", None, "heads", None)
        s = jnp.einsum("bqhd,bshd->bhqs",
                       q.astype(jnp.float32) * scale, k.astype(jnp.float32))
        # primary: shard scores over heads; fallback "attn_q" shards the
        # query rows instead when H doesn't divide the model axis (e.g.
        # 40 or 20 heads on a 16-way axis) — the conflict resolver in
        # spec_for gives heads priority, so this is a no-op otherwise.
        s = shard(s, "batch", "heads", "attn_q", None)
        q_pos = jnp.arange(Sq)[:, None] + q_offset
        k_pos = jnp.arange(Skv)[None, :] + k_offset
        mask = jnp.ones((Sq, Skv), dtype=bool)
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask[None, None], s, NEG_INF)
        if kv_len is not None:
            s = jnp.where((k_pos < kv_len[:, None])[:, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqs,bshd->bqhd", p, v.astype(jnp.float32))
        return o.astype(q.dtype)


# --------------------------------------------------------------------------
# Decode attention (one new token against a KV cache)
# --------------------------------------------------------------------------

def decode_attention(
    q: jax.Array,          # (B, 1, H, D)
    k: jax.Array,          # (B, L, KV, D) cache
    v: jax.Array,          # (B, L, KV, D)
    *,
    kv_len: jax.Array,     # (B,) number of valid cache entries
    window: int | None = None,
    scale: float | None = None,
    impl: Impl | None = None,
    blk_k: int | None = None,
    k_new: jax.Array | None = None,   # (B, 1, KV, D)
    v_new: jax.Array | None = None,   # (B, 1, KV, D)
) -> jax.Array:
    """One query row per batch row against its ``kv_len`` cached rows.

    ``k_new``/``v_new`` are the current token's own key and value, not
    yet written to the cache: they are attended as one more score column
    after the cached rows (position ``kv_len``), merged in the softmax,
    so the caller can leave the cache untouched until after the step.
    """
    impl = _resolve(impl)
    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels import decode_attention as da
        if k_new is not None:
            # the kernel attends cached rows only: put the token's row
            # into a copy of the layer
            rows = jnp.arange(q.shape[0])
            k = k.at[rows, kv_len].set(k_new[:, 0].astype(k.dtype))
            v = v.at[rows, kv_len].set(v_new[:, 0].astype(v.dtype))
            kv_len = kv_len + 1
        if blk_k is None:
            from repro.kernels import autotune
            blk_k = autotune.decode_tiling(k.shape[1], q.shape[-1],
                                           str(q.dtype))["blk_k"]
        return da.decode_attention(q, k, v, kv_len=kv_len, window=window,
                                   scale=scale, blk_k=blk_k,
                                   interpret=(impl == "pallas_interpret"))
    B, _, H, D = q.shape
    _, L, KV, _ = k.shape
    G = H // KV
    scale = (1.0 / D**0.5) if scale is None else scale
    # tokens attended in all: the cached rows, and the new one if given
    n = kv_len if k_new is None else kv_len + 1
    with jax.named_scope("flashable_decode"):
        qs = (q[:, 0].astype(jnp.float32) * scale).reshape(B, KV, G, D)
        s = jnp.einsum("bkgd,bskd->bkgs", qs, k.astype(jnp.float32))
        k_pos = jnp.arange(L)[None, :]
        valid = k_pos < kv_len[:, None]
        if window is not None:
            valid &= k_pos > (n[:, None] - 1 - window)
        s = jnp.where(valid[:, None, None], s, NEG_INF)
        if k_new is None:
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bkgs,bskd->bkgd", p, v.astype(jnp.float32))
        else:
            s_new = jnp.einsum("bkgd,bkd->bkg", qs,
                               k_new[:, 0].astype(jnp.float32))
            p, p_new = softmax_with_column(s, s_new)
            o = (jnp.einsum("bkgs,bskd->bkgd", p, v.astype(jnp.float32))
                 + p_new[..., None] * v_new[:, 0, :, None].astype(jnp.float32))
        return o.reshape(B, 1, H, D).astype(q.dtype)


def softmax_with_column(s: jax.Array, s_new: jax.Array):
    """Softmax over the last axis of ``s`` with ``s_new`` (``s.shape[:-1]``)
    as one more column: returns the probabilities of ``s``'s columns and
    of the extra one, which sum to one."""
    m = jnp.maximum(jnp.max(s, axis=-1), s_new)
    e = jnp.exp(s - m[..., None])
    e_new = jnp.exp(s_new - m)
    den = jnp.sum(e, axis=-1) + e_new
    return e / den[..., None], e_new / den


# --------------------------------------------------------------------------
# Selective SSM scan (Mamba)
# --------------------------------------------------------------------------

def mamba_scan(
    delta: jax.Array,   # (B, S, Di)
    A: jax.Array,       # (Di, N)
    Bt: jax.Array,      # (B, S, N)
    Ct: jax.Array,      # (B, S, N)
    x: jax.Array,       # (B, S, Di)
    h0: jax.Array | None = None,
    *,
    impl: Impl | None = None,
    chunk: int = 256,
) -> tuple[jax.Array, jax.Array]:
    impl = _resolve(impl)
    if impl == "ref":
        return _ref.mamba_scan(delta, A, Bt, Ct, x, h0)
    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels import linear_scan as ls
        return ls.mamba_scan(delta, A, Bt, Ct, x, h0,
                             interpret=(impl == "pallas_interpret"))
    return _xla_mamba_scan(delta, A, Bt, Ct, x, h0, chunk=chunk)


def _first_order_combine(e1, e2):
    a1, b1 = e1
    a2, b2 = e2
    return a2 * a1, a2 * b1 + b2


def _xla_mamba_scan(delta, A, Bt, Ct, x, h0, *, chunk):
    """Chunked scan: lax.scan over chunks of ``chunk`` steps; inside a chunk
    an associative scan over the first-order recurrence. The (B,C,Di,N)
    tensors are materialized only per-chunk, bounding memory, and only one
    state per chunk boundary is saved for the backward pass."""
    B, S, Di = delta.shape
    N = A.shape[1]
    C = min(chunk, S)
    n = -(-S // C)
    pad = n * C - S

    def pad_t(t):
        return jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2)) if pad else t

    dl, bt, ct, xs = (pad_t(t).reshape(B, n, C, -1).transpose(1, 0, 2, 3)
                      for t in (delta, Bt, Ct, x))
    h = (jnp.zeros((B, Di, N), jnp.float32) if h0 is None
         else h0.astype(jnp.float32))

    def chunk_fn(h, inp):
        with jax.named_scope("flashable_mamba_scan"):
            dlc, btc, ctc, xc = inp        # (B, C, ·)
            dA = jnp.exp(dlc.astype(jnp.float32)[..., None] * A[None, None])      # (B,C,Di,N)
            dBx = ((dlc * xc).astype(jnp.float32)[..., None]
                   * btc.astype(jnp.float32)[:, :, None])                          # (B,C,Di,N)
            # fold carry into the first element
            dBx = dBx.at[:, 0].add(dA[:, 0] * h)
            a_cum, h_all = jax.lax.associative_scan(_first_order_combine,
                                                    (dA, dBx), axis=1)
            y = jnp.einsum("bcdn,bcn->bcd", h_all, ctc.astype(jnp.float32))
            return h_all[:, -1], y

    h, ys = jax.lax.scan(chunk_fn, h, (dl, bt, ct, xs))
    y = ys.transpose(1, 0, 2, 3).reshape(B, n * C, Di)[:, :S]
    return y.astype(x.dtype), h


# --------------------------------------------------------------------------
# RWKV6 linear-attention scan (data-dependent decay, matrix state)
# --------------------------------------------------------------------------

def rwkv_scan(
    r: jax.Array,   # (B, S, H, K)
    w: jax.Array,   # (B, S, H, K) decay in (0, 1)
    k: jax.Array,   # (B, S, H, K)
    v: jax.Array,   # (B, S, H, V)
    u: jax.Array,   # (H, K)
    h0: jax.Array | None = None,
    *,
    impl: Impl | None = None,
    chunk: int = 64,
) -> tuple[jax.Array, jax.Array]:
    impl = _resolve(impl)
    if impl == "ref":
        return _ref.rwkv_scan(r, w, k, v, u, h0)
    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels import linear_scan as ls
        return ls.rwkv_scan(r, w, k, v, u, h0,
                            interpret=(impl == "pallas_interpret"))
    return _xla_rwkv_scan(r, w, k, v, u, h0, chunk=chunk)


def _xla_rwkv_scan(r, w, k, v, u, h0, *, chunk):
    """Chunked associative scan of h_t = diag(w_t) h_{t-1} + k_t v_t^T."""
    B, S, H, K = r.shape
    V = v.shape[-1]
    C = min(chunk, S)
    n = -(-S // C)
    pad = n * C - S

    def pad_t(t, one_pad=False):
        if not pad:
            return t
        cfg = [(0, 0), (0, pad), (0, 0), (0, 0)]
        t = jnp.pad(t, cfg, constant_values=1.0 if one_pad else 0.0)
        return t

    rc = pad_t(r).reshape(B, n, C, H, K).transpose(1, 0, 2, 3, 4)
    wc = pad_t(w, one_pad=True).reshape(B, n, C, H, K).transpose(1, 0, 2, 3, 4)
    kc = pad_t(k).reshape(B, n, C, H, K).transpose(1, 0, 2, 3, 4)
    vc = pad_t(v).reshape(B, n, C, H, V).transpose(1, 0, 2, 3, 4)
    h = (jnp.zeros((B, H, K, V), jnp.float32) if h0 is None
         else h0.astype(jnp.float32))
    uf = u.astype(jnp.float32)

    def chunk_fn(h, inp):
        with jax.named_scope("flashable_rwkv_scan"):
            rr, ww, kk, vv = (t.astype(jnp.float32) for t in inp)   # (B,C,H,·)
            kv = kk[..., :, None] * vv[..., None, :]                # (B,C,H,K,V)
            a = ww[..., :, None]                                    # (B,C,H,K,1)
            b = kv.at[:, 0].add(a[:, 0] * h)
            _, h_all = jax.lax.associative_scan(_first_order_combine, (a, b),
                                                axis=1)
            h_prev = jnp.concatenate([h[:, None], h_all[:, :-1]], axis=1)
            o = jnp.einsum("bchk,bchkv->bchv", rr,
                           h_prev + uf[None, None, :, :, None] * kv)
            return h_all[:, -1], o

    h, os_ = jax.lax.scan(chunk_fn, h, (rc, wc, kc, vc))
    o = os_.transpose(1, 0, 2, 3, 4).reshape(B, n * C, H, V)[:, :S]
    return o.astype(v.dtype), h


def rwkv_decode_step(r, w, k, v, u, h):
    """Single-token RWKV update. r/w/k: (B,H,K), v: (B,H,V), h: (B,H,K,V)."""
    rf, wf, kf, vf = (t.astype(jnp.float32) for t in (r, w, k, v))
    kv = kf[..., :, None] * vf[..., None, :]
    o = jnp.einsum("bhk,bhkv->bhv", rf, h + u[None, :, :, None].astype(jnp.float32) * kv)
    h = wf[..., :, None] * h + kv
    return o.astype(v.dtype), h


def mamba_decode_step(delta, A, Bt, Ct, x, h):
    """Single-token Mamba update. delta/x: (B,Di), Bt/Ct: (B,N), h: (B,Di,N)."""
    dA = jnp.exp(delta.astype(jnp.float32)[..., None] * A[None])
    dBx = (delta * x).astype(jnp.float32)[..., None] * Bt.astype(jnp.float32)[:, None]
    h = dA * h + dBx
    y = jnp.einsum("bdn,bn->bd", h, Ct.astype(jnp.float32))
    return y.astype(x.dtype), h


# --------------------------------------------------------------------------
# Matmul (batched-inference contraction for the micro-batched face models)
# --------------------------------------------------------------------------

def matmul(a: jax.Array, b: jax.Array, *, bias: jax.Array | None = None,
           epilogue: str = "none", impl: Impl | None = None,
           blk_m: int | None = None, blk_n: int | None = None,
           blk_k: int | None = None) -> jax.Array:
    """(M, K) @ (K, N) with float32 accumulation.

    ``bias`` ((N,)) and ``epilogue`` (``"none"``/``"tanh"``) fuse the
    MLP tail into the contraction — on the Pallas path they run on the
    accumulator in VMEM, skipping an HBM round trip between a layer's
    matmul and its activation.

    Block sizes left as ``None`` resolve to autotuned tilings for this
    (shape, dtype) from :mod:`repro.kernels.autotune` (persistent-cache
    lookup; a miss runs the candidate sweep once and memoizes).
    """
    impl = _resolve(impl)
    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels import matmul as mm
        blocks = _tuned_matmul_blocks(a.shape, b.shape, a.dtype,
                                      blk_m, blk_n, blk_k)
        return mm.matmul(a, b, bias=bias, epilogue=epilogue, **blocks,
                         interpret=(impl == "pallas_interpret"))
    # ref and xla coincide: XLA's dot is already the memory-optimal form
    # (at HIGHEST, so the TPU keeps the float32 contract the kernel keeps)
    out = jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                  precision=jax.lax.Precision.HIGHEST)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    if epilogue == "tanh":
        out = jnp.tanh(out)
    return out.astype(a.dtype)


def _tuned_matmul_blocks(a_shape, b_shape, dtype, blk_m, blk_n, blk_k):
    """Fill unspecified block sizes from the autotune cache."""
    if blk_m is not None and blk_n is not None and blk_k is not None:
        return {"blk_m": blk_m, "blk_n": blk_n, "blk_k": blk_k}
    from repro.kernels import autotune
    tuned = autotune.matmul_tiling(a_shape[0], a_shape[1], b_shape[1],
                                   str(dtype))
    return {"blk_m": blk_m if blk_m is not None else tuned["blk_m"],
            "blk_n": blk_n if blk_n is not None else tuned["blk_n"],
            "blk_k": blk_k if blk_k is not None else tuned["blk_k"]}


# --------------------------------------------------------------------------
# Bilinear resize (video-analytics pre-processing — the paper's resize tax)
# --------------------------------------------------------------------------

def resize_bilinear(img: jax.Array, out_h: int, out_w: int,
                    *, impl: Impl | None = None,
                    blk_oh: int | None = None) -> jax.Array:
    impl = _resolve(impl)
    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels import resize as rs
        if blk_oh is None:
            from repro.kernels import autotune
            blk_oh = autotune.resize_tiling(
                img.shape[-3], img.shape[-2], out_h, out_w,
                str(img.dtype))["blk_oh"]
        return rs.resize_bilinear(img, out_h, out_w, blk_oh=blk_oh,
                                  interpret=(impl == "pallas_interpret"))
    return _ref.resize_bilinear(img, out_h, out_w)
