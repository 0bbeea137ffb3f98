"""Decoder-only LM: block-pattern scan-over-layers, train/prefill/decode.

``cfg.block_pattern`` is the repeating unit (dense: 1 layer; gemma3: 5
local + 1 global; jamba: 7 mamba + 1 attn with alternating MoE). Parameters
and caches for each pattern position are stacked over ``n_repeats`` and the
stack is consumed by one ``lax.scan`` — one trace regardless of depth, with
per-block rematerialization in training.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm
from repro.models.layers import (
    P, apply_norm, cast_params, embed_meta, embed_tokens, mlp_apply,
    mlp_meta, norm_meta, stack_meta, unembed,
)


# --------------------------------------------------------------------------
# metadata
# --------------------------------------------------------------------------

def _mixer_meta(cfg, spec):
    if spec.kind == "attn":
        return attn.attn_meta(cfg)
    if spec.kind == "mamba":
        return ssm.mamba_meta(cfg)
    return ssm.rwkv_meta(cfg)


def _mlp_meta(cfg, spec):
    if spec.moe:
        return moe_mod.moe_meta(cfg)
    if cfg.mlp_kind == "rwkv":
        return ssm.rwkv_cm_meta(cfg)
    return mlp_meta(cfg)


def block_meta(cfg) -> dict:
    out = {}
    for i, spec in enumerate(cfg.block_pattern):
        out[f"l{i}"] = {
            "ln1": norm_meta(cfg),
            "mix": _mixer_meta(cfg, spec),
            "ln2": norm_meta(cfg),
            "mlp": _mlp_meta(cfg, spec),
        }
    return out


def lm_meta(cfg) -> dict:
    return {
        "embed": embed_meta(cfg),
        "blocks": stack_meta(block_meta(cfg), cfg.n_repeats),
        "ln_f": norm_meta(cfg),
    }


def lm_cache_meta(cfg, batch: int, cache_len: int) -> dict:
    blocks = {}
    for i, spec in enumerate(cfg.block_pattern):
        if spec.kind == "attn":
            c = attn.attn_cache_meta(cfg, spec, batch, cache_len)
        elif spec.kind == "mamba":
            c = ssm.mamba_cache_meta(cfg, batch)
        else:
            c = ssm.rwkv_cache_meta(cfg, batch)
            c["x_cm"] = P((batch, cfg.d_model), ("batch", "embed"), "zeros")
        blocks[f"l{i}"] = c
    return {"blocks": stack_meta(blocks, cfg.n_repeats)}


# --------------------------------------------------------------------------
# layer application
# --------------------------------------------------------------------------

def _apply_layer_train(cfg, spec, lp, x, positions, aux):
    h = apply_norm(cfg, lp["ln1"], x)
    if spec.kind == "attn":
        mix = attn.attn_apply(cfg, spec, lp["mix"], h, positions)
    elif spec.kind == "mamba":
        mix = ssm.mamba_apply(cfg, lp["mix"], h)
    else:
        mix = ssm.rwkv_apply(cfg, lp["mix"], h)
    x = shard(x + mix, "batch", "seq", None)
    h = apply_norm(cfg, lp["ln2"], x)
    if spec.moe:
        out, a = moe_mod.moe_apply(cfg, lp["mlp"], h)
        aux = aux + a
    elif cfg.mlp_kind == "rwkv":
        out = ssm.rwkv_cm_apply(cfg, lp["mlp"], h)
    else:
        out = mlp_apply(cfg, lp["mlp"], h)
    x = shard(x + out, "batch", "seq", None)
    return x, aux


def _apply_layer_prefill(cfg, spec, lp, x, positions, cache_len, aux):
    h = apply_norm(cfg, lp["ln1"], x)
    if spec.kind == "attn":
        mix, cache = attn.attn_prefill(cfg, spec, lp["mix"], h, positions,
                                       cache_len)
    elif spec.kind == "mamba":
        mix, cache = ssm.mamba_apply(cfg, lp["mix"], h, return_cache=True)
    else:
        mix, cache = ssm.rwkv_apply(cfg, lp["mix"], h, return_cache=True)
    x = x + mix
    h = apply_norm(cfg, lp["ln2"], x)
    if spec.moe:
        out, a = moe_mod.moe_apply(cfg, lp["mlp"], h)
        aux = aux + a
    elif cfg.mlp_kind == "rwkv":
        out = ssm.rwkv_cm_apply(cfg, lp["mlp"], h)
        cache["x_cm"] = h[:, -1]
    else:
        out = mlp_apply(cfg, lp["mlp"], h)
    x = x + out
    return x, cache, aux


def _apply_layer_decode(cfg, spec, lp, x, cache, cur_len, layer):
    # named scopes (``attn``/``mamba``/``rwkv``, ``moe``/``mlp``) tag the
    # ops' metadata only, so a trace viewer can tell which part of the
    # step owns an op. An attention layer's ``cache`` is the whole stack
    # and it returns its new rows (see ``attn_decode``); a recurrent
    # layer's is its own state, returned updated.
    h = apply_norm(cfg, lp["ln1"], x)
    with jax.named_scope(spec.kind):
        if spec.kind == "attn":
            mix, cache = attn.attn_decode(cfg, spec, lp["mix"], h, cache,
                                          cur_len, layer)
        elif spec.kind == "mamba":
            mix, cache = ssm.mamba_decode(cfg, lp["mix"], h, cache)
        else:
            mix, new = ssm.rwkv_decode(cfg, lp["mix"], h,
                                       {k: cache[k] for k in ("x_tm", "h")})
            cache = {**cache, **new}
    x = x + mix
    h = apply_norm(cfg, lp["ln2"], x)
    with jax.named_scope("moe" if spec.moe else "mlp"):
        if spec.moe:
            out, _ = moe_mod.moe_apply(cfg, lp["mlp"], h)
        elif cfg.mlp_kind == "rwkv":
            out = ssm.rwkv_cm_decode(cfg, lp["mlp"], h, cache["x_cm"])
            cache = {**cache, "x_cm": h[:, 0]}
        else:
            out = mlp_apply(cfg, lp["mlp"], h)
    x = x + out
    return x, cache


# --------------------------------------------------------------------------
# full model passes
# --------------------------------------------------------------------------

def lm_forward(cfg, params, tokens, *, remat: bool = True):
    """Train-mode forward. Returns (hidden (B,S,d), aux_loss)."""
    dtype = jnp.dtype(cfg.dtype)
    params = cast_params(params, dtype)
    B, S = tokens.shape
    x = embed_tokens(cfg, params["embed"], tokens, dtype)
    x = shard(x, "batch", "seq", None)
    positions = jnp.arange(S)

    def block_fn(carry, bp):
        x, aux = carry
        for i, spec in enumerate(cfg.block_pattern):
            x, aux = _apply_layer_train(cfg, spec, bp[f"l{i}"], x,
                                        positions, aux)
        # sequence-parallel layer boundary: the saved-for-backward residual
        # stream is sharded over the model axis (Megatron SP); recovered by
        # an all-gather inside the (remat'd) block.
        x = shard(x, "batch", "seq_block", None)
        return (x, aux), None

    fn = jax.checkpoint(block_fn, prevent_cse=False) if remat else block_fn
    (x, aux), _ = jax.lax.scan(fn, (x, jnp.zeros((), jnp.float32)),
                               params["blocks"])
    x = apply_norm(cfg, params["ln_f"], x)
    return x, aux


def lm_logits(cfg, params, hidden):
    params = cast_params(params, jnp.dtype(cfg.dtype))
    return unembed(cfg, params["embed"], hidden)


def lm_loss(cfg, params, tokens, labels, *, chunk: int = 512,
            remat: bool = True):
    """Chunked softmax cross-entropy (never materializes (B,S,V) at once)."""
    hidden, aux = lm_forward(cfg, params, tokens, remat=remat)
    dtype = jnp.dtype(cfg.dtype)
    emb = cast_params(params["embed"], dtype)
    B, S, d = hidden.shape
    C = min(chunk, S)
    n = S // C if S % C == 0 else -(-S // C)
    pad = n * C - S
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    hs = hidden.reshape(B, n, C, d).transpose(1, 0, 2, 3)
    ls = labels.reshape(B, n, C).transpose(1, 0, 2)

    def chunk_loss(carry, inp):
        h, lab = inp
        logits = unembed(cfg, emb, h).astype(jnp.float32)
        logits = shard(logits, "batch", "seq", "vocab")
        lse = jax.nn.logsumexp(logits, axis=-1)
        lab_c = jnp.clip(lab, 0)
        ll = jnp.take_along_axis(logits, lab_c[..., None], axis=-1)[..., 0]
        valid = (lab >= 0).astype(jnp.float32)
        carry = (carry[0] + jnp.sum((lse - ll) * valid), carry[1] + valid.sum())
        return carry, None

    fn = jax.checkpoint(chunk_loss, prevent_cse=False) if remat else chunk_loss
    (tot, cnt), _ = jax.lax.scan(fn, (jnp.zeros(()), jnp.zeros(())), (hs, ls))
    return tot / jnp.maximum(cnt, 1.0) + aux


def lm_prefill(cfg, params, tokens, *, cache_len: int | None = None):
    """Returns (last-position logits (B,V), cache)."""
    dtype = jnp.dtype(cfg.dtype)
    params = cast_params(params, dtype)
    B, S = tokens.shape
    cache_len = cache_len or S
    x = embed_tokens(cfg, params["embed"], tokens, dtype)
    positions = jnp.arange(S)

    def block_fn(carry, bp):
        x, aux = carry
        caches = {}
        for i, spec in enumerate(cfg.block_pattern):
            x, c, aux = _apply_layer_prefill(cfg, spec, bp[f"l{i}"], x,
                                             positions, cache_len, aux)
            caches[f"l{i}"] = c
        return (x, aux), caches

    (x, _), caches = jax.lax.scan(block_fn, (x, jnp.zeros((), jnp.float32)),
                                  params["blocks"])
    x = apply_norm(cfg, params["ln_f"], x)
    logits = unembed(cfg, params["embed"], x[:, -1:])[:, 0]
    return logits, {"blocks": caches, "cur_len": jnp.asarray(S, jnp.int32)}


def _lm_decode_blocks(cfg, params, blocks, tokens, cur_len):
    """Shared decode body: one token per row against the block caches.

    ``cur_len`` is scalar (lock-step) or ``(B,)`` (ragged slots); the
    attention layers handle either form (see ``attn_decode``).

    The attention caches stay out of the layer scan: each layer reads its
    rows where they lie in the stack and hands back only its new rows,
    which are written into the stack after the scan. So the step never
    slices a layer's block out of the stack or stacks it back. Recurrent
    states (a few KB a row, rewritten whole each step) are scanned as
    ``xs``/``ys``.
    """
    dtype = jnp.dtype(cfg.dtype)
    params = cast_params(params, dtype)
    x = embed_tokens(cfg, params["embed"], tokens, dtype)
    names = [f"l{i}" for i in range(len(cfg.block_pattern))]
    attn_pos = {n for n, spec in zip(names, cfg.block_pattern)
                if spec.kind == "attn"}
    rec = {n: blocks[n] for n in names if n not in attn_pos}

    def block_fn(x, xs):
        bp, rc, layer = xs
        new = {}
        for n, spec in zip(names, cfg.block_pattern):
            cache = blocks[n] if n in attn_pos else rc[n]
            x, new[n] = _apply_layer_decode(cfg, spec, bp[n], x, cache,
                                            cur_len, layer)
        return x, new

    layers = jnp.arange(cfg.n_repeats, dtype=jnp.int32)
    x, new = jax.lax.scan(block_fn, x, (params["blocks"], rec, layers))
    with jax.named_scope("lm_head"):
        x = apply_norm(cfg, params["ln_f"], x)
        logits = unembed(cfg, params["embed"], x[:, -1:])[:, 0]
    for n, spec in zip(names, cfg.block_pattern):
        if n in attn_pos:
            new[n] = attn.attn_write(spec, blocks[n], new[n], cur_len)
    return logits, new


def lm_decode_step(cfg, params, cache, tokens):
    """tokens: (B, 1). Returns (logits (B,V), new cache)."""
    cur_len = cache["cur_len"]
    logits, new_caches = _lm_decode_blocks(cfg, params, cache["blocks"],
                                           tokens, cur_len)
    return logits, {"blocks": new_caches, "cur_len": cur_len + 1}


def lm_decode_step_ragged(cfg, params, blocks, tokens, kv_len):
    """Continuous-batching decode: every slot at its own cache length.

    ``blocks`` is the batched block-cache tree (no ``cur_len`` — the
    scheduler owns per-slot occupancy host-side), ``tokens`` (B, 1),
    ``kv_len`` (B,) int32 tokens-so-far per slot. Returns
    (logits (B, V), new blocks); the caller advances its own lengths.
    """
    return _lm_decode_blocks(cfg, params, blocks, tokens,
                             kv_len.astype(jnp.int32))
