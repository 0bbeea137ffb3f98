"""Per-architecture smoke tests (reduced family-preserving configs) +
decode-vs-forward consistency — the core model-correctness invariant."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, SHAPES, get_config, supports_shape
from repro.models import transformer as tf
from repro.models import encdec as ed
from repro.models.model import build_model

KEY = jax.random.PRNGKey(0)


def _batch(cfg, B=2, S=16, seed=1):
    k = jax.random.PRNGKey(seed)
    tokens = jax.random.randint(k, (B, S + 1), 0, cfg.vocab_size)
    batch = {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}
    if cfg.encdec:
        batch["frames"] = jax.random.normal(k, (B, S, cfg.d_model),
                                            jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_and_loss(arch):
    """One forward + loss on CPU: output shapes right, no NaNs."""
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    params = model.init(KEY)
    batch = _batch(cfg)
    hidden, aux = model.forward(params, batch)
    B = batch["tokens"].shape[0]
    assert hidden.shape[0] == B and hidden.shape[-1] == cfg.d_model
    assert bool(jnp.all(jnp.isfinite(hidden)))
    loss = model.loss(params, batch)
    assert loss.shape == () and bool(jnp.isfinite(loss))


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step(arch):
    """One gradient step on CPU: loss finite, grads finite, params move."""
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    params = model.init(KEY)
    batch = _batch(cfg)
    loss, grads = jax.value_and_grad(model.loss)(params, batch)
    assert bool(jnp.isfinite(loss))
    gleaves = jax.tree.leaves(grads)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in gleaves)
    assert any(float(jnp.max(jnp.abs(g))) > 0 for g in gleaves)


def _no_drop(cfg):
    if cfg.moe:
        return cfg.replace(moe=dataclasses.replace(
            cfg.moe, capacity_factor=float(cfg.moe.n_experts) / cfg.moe.top_k))
    return cfg


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_matches_forward(arch):
    """Prefill + T decode steps reproduce full-forward logits (f32,
    no-drop MoE capacity — capacity dropping is the one legitimate
    difference between the batched and incremental paths)."""
    cfg = _no_drop(get_config(arch, smoke=True).replace(dtype="float32"))
    model = build_model(cfg)
    params = model.init(KEY)
    B, S, T = 2, 24, 3
    k = jax.random.PRNGKey(2)
    tokens = jax.random.randint(k, (B, S + T), 0, cfg.vocab_size)
    batch = {"tokens": tokens[:, :S]}
    if cfg.encdec:
        batch["frames"] = jax.random.normal(k, (B, 12, cfg.d_model),
                                            jnp.float32)
        hidden, _ = ed.encdec_forward(cfg, params, batch["frames"], tokens,
                                      remat=False)
    else:
        hidden, _ = tf.lm_forward(cfg, params, tokens, remat=False)
    full = tf.lm_logits(cfg, params, hidden)
    scale = float(jnp.max(jnp.abs(full))) + 1e-6
    lp, cache = model.prefill(params, batch, cache_len=S + T)
    np.testing.assert_allclose(lp, full[:, S - 1], atol=2e-4 * scale,
                               rtol=1e-4)
    for t in range(T):
        lg, cache = model.decode_step(params, cache, tokens[:, S + t:S + t + 1])
        np.testing.assert_allclose(lg, full[:, S + t], atol=2e-4 * scale,
                                   rtol=1e-4)


def test_gemma_sliding_window_masks_distant_tokens():
    """Local layers must not see past the window."""
    cfg = get_config("gemma3-12b", smoke=True).replace(
        dtype="float32", n_layers=5,
        block_pattern=tuple(
            [type(get_config("gemma3-12b").block_pattern[0])(window=4)] * 5))
    model = build_model(cfg)
    params = model.init(KEY)
    S = 20
    t1 = jax.random.randint(jax.random.PRNGKey(3), (1, S), 0, cfg.vocab_size)
    t2 = t1.at[:, 0:4].set((t1[:, 0:4] + 7) % cfg.vocab_size)
    h1, _ = tf.lm_forward(cfg, params, t1, remat=False)
    h2, _ = tf.lm_forward(cfg, params, t2, remat=False)
    # with window 4 and 5 layers, receptive field = 5*(4-1)=15 < 19
    np.testing.assert_allclose(h1[:, -1], h2[:, -1], atol=1e-5)


def test_moe_capacity_drops_are_bounded():
    """Even with drops, MoE output stays finite and close in norm."""
    cfg = get_config("granite-moe-3b-a800m", smoke=True).replace(dtype="float32")
    model = build_model(cfg)
    params = model.init(KEY)
    batch = _batch(cfg, B=2, S=32)
    hidden, aux = model.forward(params, batch)
    assert bool(jnp.all(jnp.isfinite(hidden)))
    assert float(aux) >= 0.0


def test_mla_cache_is_compressed():
    """DeepSeek MLA decode cache must be the low-rank latent, not full KV."""
    cfg = get_config("deepseek-v2-236b", smoke=True)
    model = build_model(cfg)
    cache = model.abstract_cache(batch=2, cache_len=16)
    layer = cache["blocks"]["l0"]
    assert set(layer) == {"ckv", "kr"}
    assert layer["ckv"].shape[-1] == cfg.mla.kv_lora
    full_kv = 2 * cfg.n_heads * cfg.head_dim
    assert layer["ckv"].shape[-1] + layer["kr"].shape[-1] < full_kv / 4


def test_param_counts_match_init():
    """cfg.param_counts() total tracks the real initialized count."""
    for arch in ("llama3-8b", "granite-moe-3b-a800m", "jamba-v0.1-52b"):
        cfg = get_config(arch, smoke=True)
        model = build_model(cfg)
        n_real = model.n_params()
        n_est = cfg.param_counts()["total"]
        assert abs(n_real - n_est) / n_real < 0.35, (arch, n_real, n_est)


@pytest.mark.parametrize("arch", ARCHS)
def test_input_specs_cover_all_shapes(arch):
    cfg = get_config(arch)
    model = build_model(cfg)
    for name, shape in SHAPES.items():
        if not supports_shape(cfg, name):
            continue
        specs = model.input_specs(shape)
        assert "tokens" in specs
        for s in specs.values():
            assert all(d > 0 for d in s.shape)


# --------------------------------------------------------------------------
# the decode step against the formulation it replaced
# --------------------------------------------------------------------------

def _restacked_attn(cfg, spec, p, x, cache, cur_len):
    """One attention layer as the decode step used to run it: the token's
    row written into the layer's own cache block, then every valid row of
    the block attended. ``cache`` is this layer's block."""
    from repro.kernels import ops
    from repro.models import attention as at
    B = x.shape[0]
    ragged = jnp.ndim(cur_len) == 1
    pos = (cur_len[:, None] if ragged
           else jnp.full((B, 1), cur_len)).astype(jnp.int32)
    rows = jnp.arange(B)
    if cfg.mla is not None:
        m, H = cfg.mla, cfg.n_heads
        q_nope, q_rope, ckv_t, kr_t = at._mla_project(cfg, p, x, pos)
        slot = jnp.broadcast_to(cur_len, (B,))
        ckv = cache["ckv"].at[rows, slot].set(ckv_t[:, 0])
        kr = cache["kr"].at[rows, slot].set(kr_t[:, 0])
        wkv_b = p["wkv_b"].reshape(m.kv_lora, H, m.qk_nope + m.v_head)
        q_lat = jnp.einsum("bhd,lhd->bhl", q_nope[:, 0], wkv_b[..., :m.qk_nope])
        s = (jnp.einsum("bhl,bsl->bhs", q_lat.astype(jnp.float32),
                        ckv.astype(jnp.float32))
             + jnp.einsum("bhr,bsr->bhs", q_rope[:, 0].astype(jnp.float32),
                          kr.astype(jnp.float32))) * (m.qk_nope + m.qk_rope) ** -0.5
        k_pos = jnp.arange(ckv.shape[1])
        s = jnp.where(k_pos[None, None] <= slot[:, None, None], s, ops.NEG_INF)
        o_lat = jnp.einsum("bhs,bsl->bhl", jax.nn.softmax(s, axis=-1),
                           ckv.astype(jnp.float32))
        o = jnp.einsum("bhl,lhv->bhv", o_lat.astype(x.dtype),
                       wkv_b[..., m.qk_nope:])
        return o.reshape(B, 1, -1) @ p["wo"], {"ckv": ckv, "kr": kr}
    q, k, v = at._project_qkv(cfg, p, x, pos)
    L = cache["k"].shape[2]                          # (B, KV, L, D)
    slot = jnp.broadcast_to(cur_len % L if spec.window else cur_len, (B,))
    ck = cache["k"].at[rows, :, slot].set(k[:, 0])
    cv = cache["v"].at[rows, :, slot].set(v[:, 0])
    K, V = jnp.swapaxes(ck, 1, 2), jnp.swapaxes(cv, 1, 2)
    if spec.window:
        s_idx = jnp.arange(L)[None]
        t = jnp.broadcast_to(cur_len, (B,))[:, None]
        valid = s_idx + L * ((t - s_idx) // L) >= 0
        Hq, KVh, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        qs = (q[:, 0].astype(jnp.float32) * D**-0.5).reshape(B, KVh, Hq // KVh, D)
        s = jnp.einsum("bkgd,bskd->bkgs", qs, K.astype(jnp.float32))
        s = jnp.where(valid[:, None, None], s, ops.NEG_INF)
        o = jnp.einsum("bkgs,bskd->bkgd", jax.nn.softmax(s, axis=-1),
                       V.astype(jnp.float32)).astype(q.dtype)
    else:
        o = ops.decode_attention(q, K, V, kv_len=slot + 1)
    return o.reshape(B, 1, -1) @ p["wo"], {"k": ck, "v": cv}


def _restacked_decode(cfg, params, blocks, tokens, cur_len):
    """The decode step as it was: the scan slices each layer's cache
    block out of the stack (``xs``) and stacks the updated block back
    (``ys``)."""
    from repro.models.layers import (apply_norm, cast_params, embed_tokens,
                                     mlp_apply, unembed)
    from repro.models.moe import moe_apply
    dtype = jnp.dtype(cfg.dtype)
    params = cast_params(params, dtype)
    x = embed_tokens(cfg, params["embed"], tokens, dtype)

    def block_fn(x, xs):
        bp, bc = xs
        new = {}
        for i, spec in enumerate(cfg.block_pattern):
            n, lp = f"l{i}", bp[f"l{i}"]
            if spec.kind != "attn":
                x, new[n] = tf._apply_layer_decode(cfg, spec, lp, x, bc[n],
                                                   cur_len, None)
                continue
            mix, new[n] = _restacked_attn(cfg, spec, lp["mix"],
                                          apply_norm(cfg, lp["ln1"], x),
                                          bc[n], cur_len)
            x = x + mix
            h = apply_norm(cfg, lp["ln2"], x)
            x = x + (moe_apply(cfg, lp["mlp"], h)[0] if spec.moe
                     else mlp_apply(cfg, lp["mlp"], h))
        return x, new

    x, new = jax.lax.scan(block_fn, x, (params["blocks"], blocks))
    x = apply_norm(cfg, params["ln_f"], x)
    return unembed(cfg, params["embed"], x[:, -1:])[:, 0], new


@pytest.mark.parametrize("lengths", ["ragged", "scalar"])
@pytest.mark.parametrize("arch", ["llama3-8b", "gemma3-12b", "deepseek-v2-236b",
                                  "jamba-v0.1-52b", "rwkv6-3b"])
def test_decode_step_matches_restacked_formulation(arch, lengths):
    """Reading the stacked cache in place and writing each layer's row
    after the scan gives the logits and caches of slicing each layer's
    block out, writing the row into it and stacking it back: global
    attention, a sliding window, MLA, mamba and rwkv; ragged lengths
    from an empty slot to the cache's second-to-last row, and one
    lock-step length."""
    base = get_config(arch, smoke=True)
    cfg = _no_drop(base.replace(dtype="float32",
                                n_layers=2 * len(base.block_pattern)))
    model = build_model(cfg)
    params = model.init(KEY)
    B, cache_len = 4, 16
    blocks = model.init_cache(B, cache_len)["blocks"]
    leaves, tree = jax.tree.flatten(blocks)
    keys = jax.random.split(jax.random.PRNGKey(5), len(leaves))
    blocks = tree.unflatten([jax.random.normal(k, l.shape, l.dtype)
                             for k, l in zip(keys, leaves)])
    tokens = jax.random.randint(jax.random.PRNGKey(6), (B, 1), 0,
                                cfg.vocab_size)
    cur_len = (jnp.array([0, 5, cache_len - 2, 11], jnp.int32)
               if lengths == "ragged" else jnp.asarray(9, jnp.int32))
    got = jax.jit(functools.partial(tf._lm_decode_blocks, cfg))(
        params, blocks, tokens, cur_len)
    want = jax.jit(functools.partial(_restacked_decode, cfg))(
        params, blocks, tokens, cur_len)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)
