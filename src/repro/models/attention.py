"""Attention mixers: GQA/MHA, sliding-window, MLA (DeepSeek-V2).

Three entry modes per layer:
  * train:   full forward, no cache.
  * prefill: full forward, returns the layer's decode cache.
  * decode:  one new token against the cache, returns updated cache.

Caches are sequence-sharded under the serve rules ("kv_seq" -> model axis);
the decode softmax then reduces over a sharded axis, which GSPMD lowers to
local partial reductions + small all-reduces (distributed-LSE) instead of
gathering the cache.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard
from repro.kernels import ops
from repro.models.layers import P, norm_meta, apply_norm, rope


# --------------------------------------------------------------------------
# parameter metadata
# --------------------------------------------------------------------------

def attn_meta(cfg) -> dict:
    d, H, KV, D = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if cfg.mla is not None:
        m = cfg.mla
        meta = {
            "wq_a": P((d, m.q_lora), ("embed", "lora")),
            "q_norm": norm_meta(cfg, m.q_lora),
            "wq_b": P((m.q_lora, H * (m.qk_nope + m.qk_rope)), ("lora", "heads")),
            "wkv_a": P((d, m.kv_lora + m.qk_rope), ("embed", None)),
            "kv_norm": norm_meta(cfg, m.kv_lora),
            "wkv_b": P((m.kv_lora, H * (m.qk_nope + m.v_head)), ("lora", "heads")),
            "wo": P((H * m.v_head, d), ("heads", "embed")),
        }
        return meta
    meta = {
        "wq": P((d, H * D), ("embed", "heads")),
        "wk": P((d, KV * D), ("embed", "kv_heads")),
        "wv": P((d, KV * D), ("embed", "kv_heads")),
        "wo": P((H * D, d), ("heads", "embed")),
    }
    if cfg.qkv_bias:
        meta["bq"] = P((H * D,), ("heads",), "zeros")
        meta["bk"] = P((KV * D,), ("kv_heads",), "zeros")
        meta["bv"] = P((KV * D,), ("kv_heads",), "zeros")
    if cfg.qk_norm:
        meta["qn"] = norm_meta(cfg, D)
        meta["kn"] = norm_meta(cfg, D)
    return meta


def attn_cache_meta(cfg, spec, batch: int, cache_len: int) -> dict:
    """Decode-cache metadata for one attention layer (as P entries)."""
    if cfg.mla is not None:
        m = cfg.mla
        return {"ckv": P((batch, cache_len, m.kv_lora),
                         ("batch", "kv_seq", None), "zeros"),
                "kr": P((batch, cache_len, m.qk_rope),
                        ("batch", "kv_seq", None), "zeros")}
    # heads before positions: each head's (L, D) rows are one matrix,
    # which the decode step's dots read as stored
    KV, D = cfg.n_kv_heads, cfg.head_dim
    L = min(spec.window, cache_len) if spec.window else cache_len
    return {"k": P((batch, KV, L, D), ("batch", "kv_heads", "kv_seq", None), "zeros"),
            "v": P((batch, KV, L, D), ("batch", "kv_heads", "kv_seq", None), "zeros")}


# --------------------------------------------------------------------------
# GQA forward
# --------------------------------------------------------------------------

def _project_qkv(cfg, p, x, positions):
    B, S, d = x.shape
    H, KV, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    q = q.reshape(B, S, H, D)
    k = k.reshape(B, S, KV, D)
    v = v.reshape(B, S, KV, D)
    if cfg.qk_norm:
        q = apply_norm(cfg, p["qn"], q)
        k = apply_norm(cfg, p["kn"], k)
    if cfg.pos == "rope":
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_apply(cfg, spec, p, x, positions):
    """Full-sequence (train) attention."""
    if cfg.mla is not None:
        return _mla_apply(cfg, p, x, positions)[0]
    q, k, v = _project_qkv(cfg, p, x, positions)
    q = shard(q, "batch", "seq", "heads", None)
    k = shard(k, "batch", "seq", "kv_heads", None)
    o = ops.attention(q, k, v, causal=True, window=spec.window)
    B, S = x.shape[:2]
    return o.reshape(B, S, -1) @ p["wo"]


def attn_prefill(cfg, spec, p, x, positions, cache_len: int):
    """Forward + build this layer's decode cache (length ``cache_len``)."""
    if cfg.mla is not None:
        y, (ckv, kr) = _mla_apply(cfg, p, x, positions)
        return y, {"ckv": _fit(ckv, cache_len), "kr": _fit(kr, cache_len)}
    q, k, v = _project_qkv(cfg, p, x, positions)
    q = shard(q, "batch", "seq", "heads", None)
    k = shard(k, "batch", "seq", "kv_heads", None)
    o = ops.attention(q, k, v, causal=True, window=spec.window)
    B, S = x.shape[:2]
    y = o.reshape(B, S, -1) @ p["wo"]
    if spec.window and cache_len >= spec.window:
        cache = {"k": _roll_window(k, spec.window),
                 "v": _roll_window(v, spec.window)}
    else:
        cache = {"k": _fit(k, cache_len), "v": _fit(v, cache_len)}
    cache = {n: shard(jnp.swapaxes(c, 1, 2), "batch", "kv_heads", "kv_seq",
                      None)
             for n, c in cache.items()}
    return y, cache


def _fit(t, L):
    """Pad/trim a (B, S, ...) tensor to cache length L along axis 1."""
    S = t.shape[1]
    if S == L:
        return t
    if S > L:
        return t[:, -L:]
    pad = [(0, 0)] * t.ndim
    pad[1] = (0, L - S)
    return jnp.pad(t, pad)


def _roll_window(t, W):
    """Last W entries arranged so slot = position % W (rolling cache)."""
    S = t.shape[1]
    tail = t[:, S - W:]
    slots = jnp.arange(S - W, S) % W
    out = jnp.zeros_like(tail)
    return out.at[:, slots].set(tail)


def attn_decode(cfg, spec, p, x, cache, cur_len, layer):
    """One-token decode. x: (B, 1, d).

    ``cache`` is the whole stack of this pattern position's caches,
    leaves ``(n_repeats, B, KV, L, D)``, and ``layer`` indexes it: the
    layer's rows are read where they lie, and the cache is not written
    here. The token's own key and value are attended as one more score
    column after the cached rows; they are returned as this layer's new
    rows, ``(B, ...)`` per leaf, which :func:`attn_write` puts into the
    stack once every layer has run.

    ``cur_len`` is the tokens-so-far count — a scalar (the classic
    lock-step cache where every row is at the same position) or a
    ``(B,)`` vector for continuous batching, where each slot of the
    batched cache sits at its own length: positions, the cache write,
    and the validity mask are then all per-row, and the ragged
    ``kv_len`` flows straight into :func:`ops.decode_attention` (the
    Pallas ragged decode kernel's contract).
    """
    if cfg.mla is not None:
        return _mla_decode(cfg, p, x, cache, cur_len, layer)
    B = x.shape[0]
    H, KV, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ragged = jnp.ndim(cur_len) == 1
    if ragged:
        pos = cur_len.astype(jnp.int32)[:, None]
    else:
        pos = jnp.full((B, 1), cur_len, jnp.int32)
    q, k, v = _project_qkv(cfg, p, x, pos)
    # (B, L, KV, D) views of the stored (B, KV, L, D): a layout, not a copy
    ck = shard(jnp.swapaxes(_read_layer(cache["k"], layer), 1, 2),
               "batch", "kv_seq", "kv_heads", None)
    cv = shard(jnp.swapaxes(_read_layer(cache["v"], layer), 1, 2),
               "batch", "kv_seq", "kv_heads", None)
    k, v = k.astype(ck.dtype), v.astype(cv.dtype)    # the rows as stored
    L = ck.shape[1]
    if spec.window:
        # rolling cache: slot s holds position s + L*floor((t-s)/L) once
        # the token at t = cur_len is written; before that, the slot it
        # takes holds position t - L, which falls out of the window
        s_idx = jnp.arange(L)
        t = cur_len[:, None] if ragged else jnp.full((B, 1), cur_len)
        pos_of_slot = s_idx[None] + L * ((t - s_idx[None]) // L)
        valid = (pos_of_slot >= 0) & (pos_of_slot < t)
        o = _masked_decode(cfg, q, ck, cv, valid, k, v)
    else:
        kv_len = (cur_len.astype(jnp.int32) if ragged
                  else jnp.full((B,), cur_len, jnp.int32))
        o = ops.decode_attention(q, ck, cv, kv_len=kv_len, k_new=k, v_new=v)
    y = o.reshape(B, 1, H * D) @ p["wo"]
    return y, {"k": k[:, 0], "v": v[:, 0]}


def attn_write(spec, cache, rows, cur_len):
    """Write every layer's new row into the stacked cache: each leaf's
    ``rows`` ``(n_repeats, B, ...)`` go to position ``slot[b]`` of its
    sequence axis, the second to last (``k``/``v`` ``(.., KV, L, D)``,
    MLA's ``(.., L, C)``).

    ``cur_len`` is scalar (one write over the whole batch) or ``(B,)``
    (one write per batch row). Each is a dynamic-update-slice, which XLA
    performs in whatever layout the cache is stored, where a scatter
    would first re-lay the whole cache out (head_dim 64 is stored with
    the sequence axis minor)."""
    L = next(iter(cache.values())).shape[-2]
    slot = cur_len % L if spec.window else cur_len
    with jax.named_scope("kv_update"):
        return {n: _write_rows(c, rows[n], slot) for n, c in cache.items()}


def _read_layer(stack, layer):
    return jax.lax.dynamic_index_in_dim(stack, layer, 0, keepdims=False)


def _write_rows(stack, rows, slot):
    rows = jnp.expand_dims(rows.astype(stack.dtype), -2)  # (R, B, .., 1, C)
    mid = (0,) * (stack.ndim - 4)
    if jnp.ndim(slot) == 0:
        return jax.lax.dynamic_update_slice(stack, rows,
                                            (0, 0) + mid + (slot, 0))
    for b in range(rows.shape[1]):
        stack = jax.lax.dynamic_update_slice(
            stack, rows[:, b:b + 1], (0, b) + mid + (slot[b], 0))
    return stack


def _masked_decode(cfg, q, k, v, valid, k_new, v_new):
    """Decode attention with an explicit (B, L) validity mask over the
    cached rows, and the token's own ``k_new``/``v_new`` (B, 1, KV, D)
    as one more column."""
    B, _, H, D = q.shape
    L, KV = k.shape[1], k.shape[2]
    G = H // KV
    qs = (q[:, 0].astype(jnp.float32) * D**-0.5).reshape(B, KV, G, D)
    s = jnp.einsum("bkgd,bskd->bkgs", qs, k.astype(jnp.float32))
    s = jnp.where(valid[:, None, None], s, ops.NEG_INF)
    s_new = jnp.einsum("bkgd,bkd->bkg", qs, k_new[:, 0].astype(jnp.float32))
    pr, pr_new = ops.softmax_with_column(s, s_new)
    o = (jnp.einsum("bkgs,bskd->bkgd", pr, v.astype(jnp.float32))
         + pr_new[..., None] * v_new[:, 0, :, None].astype(jnp.float32))
    return o.reshape(B, 1, H, D).astype(q.dtype)


# --------------------------------------------------------------------------
# MLA (DeepSeek-V2): low-rank compressed KV cache, absorbed decode
# --------------------------------------------------------------------------

def _mla_project(cfg, p, x, positions):
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    cq = apply_norm(cfg, p["q_norm"], x @ p["wq_a"])
    q = (cq @ p["wq_b"]).reshape(B, S, H, m.qk_nope + m.qk_rope)
    q_nope, q_rope = q[..., :m.qk_nope], q[..., m.qk_nope:]
    q_rope = rope(q_rope, positions, cfg.rope_theta)
    kv = x @ p["wkv_a"]
    ckv = apply_norm(cfg, p["kv_norm"], kv[..., :m.kv_lora])
    kr = rope(kv[..., m.kv_lora:][:, :, None], positions, cfg.rope_theta)[:, :, 0]
    return q_nope, q_rope, ckv, kr


def _mla_apply(cfg, p, x, positions):
    """Training/prefill MLA: expand k/v from the compressed latent."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    q_nope, q_rope, ckv, kr = _mla_project(cfg, p, x, positions)
    kvb = (ckv @ p["wkv_b"]).reshape(B, S, H, m.qk_nope + m.v_head)
    k_nope, v = kvb[..., :m.qk_nope], kvb[..., m.qk_nope:]
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(kr[:, :, None],
                                                  (B, S, H, m.qk_rope))], axis=-1)
    q = shard(q, "batch", "seq", "heads", None)
    k = shard(k, "batch", "seq", "heads", None)
    scale = (m.qk_nope + m.qk_rope) ** -0.5
    o = ops.attention(q, k, v, causal=True, scale=scale)
    y = o.reshape(B, S, H * m.v_head) @ p["wo"]
    return y, (ckv, kr)


def _mla_decode(cfg, p, x, cache, cur_len, layer):
    """Absorbed-matrix decode: attend in the 512-d latent space.

    ``cur_len`` scalar (lock-step) or ``(B,)`` (ragged slots), and the
    stacked ``cache`` read in place with the token's own latent as one
    more column, as in :func:`attn_decode`.
    """
    m = cfg.mla
    B = x.shape[0]
    H = cfg.n_heads
    ragged = jnp.ndim(cur_len) == 1
    if ragged:
        pos = cur_len.astype(jnp.int32)[:, None]
    else:
        pos = jnp.full((B, 1), cur_len, jnp.int32)
    q_nope, q_rope, ckv_t, kr_t = _mla_project(cfg, p, x, pos)
    ckv = shard(_read_layer(cache["ckv"], layer), "batch", "kv_seq", None)
    kr = shard(_read_layer(cache["kr"], layer), "batch", "kv_seq", None)
    wkv_b = p["wkv_b"].reshape(m.kv_lora, H, m.qk_nope + m.v_head)
    wk = wkv_b[..., :m.qk_nope]            # (lora, H, nope)
    wv = wkv_b[..., m.qk_nope:]            # (lora, H, v)
    # absorb wk into q: (B,1,H,nope) x (lora,H,nope) -> (B,H,lora)
    q_lat = jnp.einsum("bhd,lhd->bhl", q_nope[:, 0], wk).astype(jnp.float32)
    q_r = q_rope[:, 0].astype(jnp.float32)
    scale = (m.qk_nope + m.qk_rope) ** -0.5
    s = (jnp.einsum("bhl,bsl->bhs", q_lat, ckv.astype(jnp.float32))
         + jnp.einsum("bhr,bsr->bhs", q_r, kr.astype(jnp.float32))) * scale
    # the token's own latent, as stored (cache dtype)
    ckv_n = ckv_t[:, 0].astype(ckv.dtype).astype(jnp.float32)
    kr_n = kr_t[:, 0].astype(kr.dtype).astype(jnp.float32)
    s_new = (jnp.einsum("bhl,bl->bh", q_lat, ckv_n)
             + jnp.einsum("bhr,br->bh", q_r, kr_n)) * scale
    k_pos = jnp.arange(ckv.shape[1])
    bound = cur_len[:, None, None] if ragged else cur_len
    s = jnp.where(k_pos[None, None, :] < bound, s, ops.NEG_INF)
    pr, pr_new = ops.softmax_with_column(s, s_new)
    o_lat = (jnp.einsum("bhs,bsl->bhl", pr, ckv.astype(jnp.float32))
             + pr_new[..., None] * ckv_n[:, None])              # (B,H,lora)
    o = jnp.einsum("bhl,lhv->bhv", o_lat.astype(x.dtype), wv)
    y = o.reshape(B, 1, H * m.v_head) @ p["wo"]
    return y, {"ckv": ckv_t[:, 0], "kr": kr_t[:, 0]}
