"""Per-kernel validation: shape/dtype sweeps + hypothesis property tests,
each asserting allclose against the pure-jnp oracle in repro.kernels.ref."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:      # deterministic single-example shim
    from hypothesis_fallback import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.linear_scan import mamba_scan, rwkv_scan
from repro.kernels.resize import resize_bilinear

KEY = jax.random.PRNGKey(0)


def _rand(shape, dtype=jnp.float32, seed=0, scale=1.0):
    return (jax.random.normal(jax.random.PRNGKey(seed), shape) * scale).astype(dtype)


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------

@pytest.mark.parametrize("B,Sq,Skv,H,KV,D,causal,window", [
    (2, 256, 256, 4, 2, 64, True, None),
    (1, 256, 256, 4, 4, 64, False, None),
    (2, 256, 256, 8, 2, 128, True, 128),
    (1, 128, 256, 4, 2, 32, True, None),
    (1, 128, 128, 2, 1, 256, True, None),
])
def test_flash_attention_vs_ref(B, Sq, Skv, H, KV, D, causal, window):
    q = _rand((B, Sq, H, D), seed=1)
    k = _rand((B, Skv, KV, D), seed=2)
    v = _rand((B, Skv, KV, D), seed=3)
    off = Skv - Sq
    out = flash_attention(q, k, v, causal=causal, window=window,
                          q_offset=off, interpret=True)
    want = ref.attention(q, k, v, causal=causal, window=window, q_offset=off)
    np.testing.assert_allclose(out, want, atol=3e-5, rtol=3e-5)


def test_flash_attention_bf16():
    q = _rand((1, 128, 4, 64), jnp.bfloat16, seed=4)
    k = _rand((1, 128, 2, 64), jnp.bfloat16, seed=5)
    v = _rand((1, 128, 2, 64), jnp.bfloat16, seed=6)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    want = ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               want.astype(jnp.float32), atol=3e-2, rtol=3e-2)


@settings(max_examples=8, deadline=None)
@given(st.integers(1, 3), st.sampled_from([64, 128, 192]),
       st.sampled_from([(4, 1), (4, 2), (4, 4)]),
       st.sampled_from([32, 64]), st.booleans())
def test_flash_attention_property(B, S, heads, D, causal):
    """Property: kernel == oracle for arbitrary GQA geometry."""
    H, KV = heads
    q = _rand((B, S, H, D), seed=S + H)
    k = _rand((B, S, KV, D), seed=S + KV)
    v = _rand((B, S, KV, D), seed=S + 7)
    out = flash_attention(q, k, v, causal=causal, interpret=True,
                          blk_q=64, blk_k=64)
    want = ref.attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, want, atol=3e-5, rtol=3e-5)


# --------------------------------------------------------------------------
# decode attention
# --------------------------------------------------------------------------

@pytest.mark.parametrize("B,L,H,KV,D,window", [
    (3, 1024, 8, 2, 64, None),
    (2, 512, 4, 4, 128, None),
    (2, 1024, 8, 2, 64, 100),
])
def test_decode_attention_vs_ref(B, L, H, KV, D, window):
    q = _rand((B, 1, H, D), seed=1)
    k = _rand((B, L, KV, D), seed=2)
    v = _rand((B, L, KV, D), seed=3)
    kv_len = jnp.asarray([L, L // 2, 17][:B])
    out = decode_attention(q, k, v, kv_len=kv_len, window=window,
                           interpret=True, blk_k=256)
    want = ops.decode_attention(q, k, v, kv_len=kv_len, window=window,
                                impl="xla")
    np.testing.assert_allclose(out, want, atol=3e-5, rtol=3e-5)


@settings(max_examples=6, deadline=None)
@given(st.integers(1, 4), st.sampled_from([256, 512]),
       st.integers(1, 200))
def test_decode_attention_kvlen_property(B, L, kvl):
    """Property: entries beyond kv_len never influence the output."""
    q = _rand((B, 1, 4, 32), seed=9)
    k = _rand((B, L, 2, 32), seed=10)
    v = _rand((B, L, 2, 32), seed=11)
    kv_len = jnp.full((B,), min(kvl, L))
    out1 = decode_attention(q, k, v, kv_len=kv_len, interpret=True, blk_k=128)
    # poison the invalid region
    mask = jnp.arange(L)[None, :, None, None] >= kv_len[:, None, None, None]
    k2 = jnp.where(mask, 1e4, k)
    v2 = jnp.where(mask, -1e4, v)
    out2 = decode_attention(q, k2, v2, kv_len=kv_len, interpret=True, blk_k=128)
    np.testing.assert_allclose(out1, out2, atol=1e-5, rtol=1e-5)


def test_decode_attention_legal_blk_k():
    """Tile legalization: largest lane-aligned divisor <= requested."""
    from repro.kernels.decode_attention import legal_blk_k
    assert legal_blk_k(512, 512) == 512
    assert legal_blk_k(512, 768) == 384      # the cache_len=768 crash
    assert legal_blk_k(512, 640) == 128
    assert legal_blk_k(512, 1024) == 512
    assert legal_blk_k(128, 1024) == 128
    assert legal_blk_k(512, 17) == 17        # no aligned divisor: exact L
    for L in (768, 640, 384, 96, 17):
        b = legal_blk_k(512, L)
        assert 0 < b <= min(512, L) and L % b == 0


def test_decode_attention_nonaligned_cache_default_tile():
    """cache_len=768 with the default (autotuned) blk_k used to crash at
    trace time on ``L % blk_k == 0``; legalization must round the tile
    down to a divisor and still match the oracle."""
    B, L = 2, 768
    q = _rand((B, 1, 4, 64), seed=20)
    k = _rand((B, L, 2, 64), seed=21)
    v = _rand((B, L, 2, 64), seed=22)
    kv_len = jnp.asarray([L, 300])
    out = decode_attention(q, k, v, kv_len=kv_len, interpret=True)
    want = ops.decode_attention(q, k, v, kv_len=kv_len, impl="xla")
    np.testing.assert_allclose(out, want, atol=3e-5, rtol=3e-5)


def test_decode_attention_kvlen_zero_row_is_zeros():
    """A slot with no valid cache (kv_len=0 — a freed/never-filled lane)
    must come back as exact zeros, not NaN from an empty softmax."""
    B, L = 3, 256
    q = _rand((B, 1, 4, 32), seed=23)
    k = _rand((B, L, 2, 32), seed=24)
    v = _rand((B, L, 2, 32), seed=25)
    kv_len = jnp.asarray([0, 128, 0])
    out = decode_attention(q, k, v, kv_len=kv_len, interpret=True, blk_k=128)
    assert bool(jnp.all(jnp.isfinite(out)))
    np.testing.assert_array_equal(np.asarray(out[0]), 0.0)
    np.testing.assert_array_equal(np.asarray(out[2]), 0.0)
    want = ops.decode_attention(q, k, v, kv_len=kv_len, impl="xla")
    np.testing.assert_allclose(out[1], want[1], atol=3e-5, rtol=3e-5)


def test_decode_attention_window_straddles_tile_boundary():
    """Sliding window [kv_len-window, kv_len) crossing a blk_k edge:
    both the partially-masked leading tile and the partially-valid
    trailing tile must agree with the oracle."""
    B, L = 2, 512
    q = _rand((B, 1, 4, 64), seed=26)
    k = _rand((B, L, 2, 64), seed=27)
    v = _rand((B, L, 2, 64), seed=28)
    # window [201, 300] straddles the 256 tile edge; [412, 511] the 384 one
    kv_len = jnp.asarray([300, 511])
    out = decode_attention(q, k, v, kv_len=kv_len, window=100,
                           interpret=True, blk_k=128)
    want = ops.decode_attention(q, k, v, kv_len=kv_len, window=100,
                                impl="xla")
    np.testing.assert_allclose(out, want, atol=3e-5, rtol=3e-5)


def test_decode_attention_heterogeneous_kvlen_batch():
    """A continuous-batching tick's worth of raggedness in one call:
    empty, single-token, mid-cache, and full slots side by side."""
    B, L = 4, 512
    q = _rand((B, 1, 8, 64), seed=29)
    k = _rand((B, L, 2, 64), seed=30)
    v = _rand((B, L, 2, 64), seed=31)
    kv_len = jnp.asarray([0, 1, 250, 512])
    out = decode_attention(q, k, v, kv_len=kv_len, interpret=True, blk_k=256)
    want = ops.decode_attention(q, k, v, kv_len=kv_len, impl="xla")
    np.testing.assert_array_equal(np.asarray(out[0]), 0.0)
    np.testing.assert_allclose(out[1:], want[1:], atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("impl,window", [("xla", None), ("xla", 100),
                                          ("pallas_interpret", None),
                                          ("pallas_interpret", 100)])
def test_decode_attention_new_token_column(impl, window):
    """The token's own key and value passed as ``k_new``/``v_new`` attend
    exactly as if written at row ``kv_len`` of the cache and attended
    with ``kv_len + 1``: an empty slot, windows straddling a tile edge,
    a cache one row short of full."""
    B, L = 4, 512
    q = _rand((B, 1, 8, 64), seed=32)
    k = _rand((B, L, 2, 64), seed=33)
    v = _rand((B, L, 2, 64), seed=34)
    k_new = _rand((B, 1, 2, 64), seed=35)
    v_new = _rand((B, 1, 2, 64), seed=36)
    kv_len = jnp.asarray([0, 299, 137, L - 1])
    rows = jnp.arange(B)
    kw = dict(window=window, impl=impl)
    if impl == "pallas_interpret":
        kw["blk_k"] = 128
    out = ops.decode_attention(q, k, v, kv_len=kv_len, k_new=k_new,
                               v_new=v_new, **kw)
    want = ops.decode_attention(q, k.at[rows, kv_len].set(k_new[:, 0]),
                                v.at[rows, kv_len].set(v_new[:, 0]),
                                kv_len=kv_len + 1, window=window, impl="xla")
    np.testing.assert_allclose(out, want, atol=3e-5, rtol=3e-5)


# --------------------------------------------------------------------------
# linear scans
# --------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,Di,N,blk_t,blk_c", [
    (2, 64, 256, 8, 16, 128),
    (1, 32, 128, 16, 8, 128),
])
def test_mamba_scan_vs_ref(B, S, Di, N, blk_t, blk_c):
    delta = jax.nn.softplus(_rand((B, S, Di), seed=1))
    A = -jnp.exp(_rand((Di, N), seed=2))
    Bt = _rand((B, S, N), seed=3)
    Ct = _rand((B, S, N), seed=4)
    x = _rand((B, S, Di), seed=5)
    h0 = _rand((B, Di, N), seed=6, scale=0.1)
    y, h = mamba_scan(delta, A, Bt, Ct, x, h0, interpret=True,
                      blk_t=blk_t, blk_c=blk_c)
    yr, hr = ref.mamba_scan(delta, A, Bt, Ct, x, h0)
    np.testing.assert_allclose(y, yr, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(h, hr, atol=2e-4, rtol=2e-4)


def test_mamba_xla_chunked_vs_ref():
    B, S, Di, N = 2, 100, 24, 4
    delta = jax.nn.softplus(_rand((B, S, Di), seed=1))
    A = -jnp.exp(_rand((Di, N), seed=2))
    Bt, Ct = _rand((B, S, N), seed=3), _rand((B, S, N), seed=4)
    x = _rand((B, S, Di), seed=5)
    y, h = ops.mamba_scan(delta, A, Bt, Ct, x, impl="xla", chunk=32)
    yr, hr = ref.mamba_scan(delta, A, Bt, Ct, x)
    np.testing.assert_allclose(y, yr, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(h, hr, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("B,S,H,K,V,blk_t", [
    (2, 64, 3, 32, 32, 16),
    (1, 48, 2, 64, 64, 16),
])
def test_rwkv_scan_vs_ref(B, S, H, K, V, blk_t):
    r = _rand((B, S, H, K), seed=1)
    w = jax.nn.sigmoid(_rand((B, S, H, K), seed=2)) * 0.5 + 0.45
    k = _rand((B, S, H, K), seed=3, scale=0.3)
    v = _rand((B, S, H, V), seed=4)
    u = _rand((H, K), seed=5, scale=0.1)
    h0 = _rand((B, H, K, V), seed=6, scale=0.1)
    o, h = rwkv_scan(r, w, k, v, u, h0, interpret=True, blk_t=blk_t)
    orf, hrf = ref.rwkv_scan(r, w, k, v, u, h0)
    np.testing.assert_allclose(o, orf, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(h, hrf, atol=2e-4, rtol=2e-4)


@settings(max_examples=6, deadline=None)
@given(st.integers(1, 2), st.sampled_from([16, 32, 48]))
def test_rwkv_chunk_invariance(B, S):
    """Property: the chunked XLA path is chunk-size invariant."""
    r = _rand((B, S, 2, 16), seed=1)
    w = jax.nn.sigmoid(_rand((B, S, 2, 16), seed=2)) * 0.5 + 0.45
    k = _rand((B, S, 2, 16), seed=3, scale=0.3)
    v = _rand((B, S, 2, 16), seed=4)
    u = _rand((2, 16), seed=5, scale=0.1)
    o1, h1 = ops.rwkv_scan(r, w, k, v, u, impl="xla", chunk=8)
    o2, h2 = ops.rwkv_scan(r, w, k, v, u, impl="xla", chunk=16)
    np.testing.assert_allclose(o1, o2, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(h1, h2, atol=1e-4, rtol=1e-4)


def test_scan_state_chaining():
    """Running two half-sequences with carried state == one full scan."""
    B, S, H, K, V = 1, 32, 2, 16, 16
    r = _rand((B, S, H, K), seed=1)
    w = jax.nn.sigmoid(_rand((B, S, H, K), seed=2)) * 0.5 + 0.45
    k = _rand((B, S, H, K), seed=3, scale=0.3)
    v = _rand((B, S, H, V), seed=4)
    u = _rand((H, K), seed=5, scale=0.1)
    o_full, h_full = ref.rwkv_scan(r, w, k, v, u)
    o1, h1 = ref.rwkv_scan(r[:, :16], w[:, :16], k[:, :16], v[:, :16], u)
    o2, h2 = ref.rwkv_scan(r[:, 16:], w[:, 16:], k[:, 16:], v[:, 16:], u, h1)
    np.testing.assert_allclose(jnp.concatenate([o1, o2], 1), o_full,
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(h2, h_full, atol=1e-5, rtol=1e-5)


# --------------------------------------------------------------------------
# resize
# --------------------------------------------------------------------------

@pytest.mark.parametrize("H,W,oh,ow", [
    (54, 96, 27, 48),      # 2x downscale (the paper's 1080->540 analogue)
    (64, 64, 128, 128),    # upscale
    (37, 53, 16, 24),      # ragged
])
def test_resize_vs_ref(H, W, oh, ow):
    img = jax.random.uniform(KEY, (2, H, W, 3), jnp.float32) * 255
    out = resize_bilinear(img, oh, ow, interpret=True)
    want = ref.resize_bilinear(img, oh, ow)
    np.testing.assert_allclose(out, want, atol=1e-4, rtol=1e-4)


@settings(max_examples=6, deadline=None)
@given(st.integers(8, 40), st.integers(8, 40))
def test_resize_identity_property(H, W):
    """Property: resizing to the same size is the identity."""
    img = jax.random.uniform(jax.random.PRNGKey(H * W), (H, W, 1))
    out = ref.resize_bilinear(img, H, W)
    np.testing.assert_allclose(out, img, atol=1e-6)


def test_attention_xla_chunk_invariance():
    q = _rand((2, 200, 4, 32), seed=1)
    k = _rand((2, 200, 2, 32), seed=2)
    v = _rand((2, 200, 2, 32), seed=3)
    a = ops.attention(q, k, v, causal=True, impl="xla", q_chunk=64)
    b = ops.attention(q, k, v, causal=True, impl="xla", q_chunk=512)
    np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


# --------------------------------------------------------------------------
# matmul
# --------------------------------------------------------------------------

@pytest.mark.parametrize("M,K,N,blk", [
    (8, 128, 128, 128),      # single tile
    (128, 512, 256, 128),    # multi-tile, k accumulation
    (13, 200, 37, 128),      # ragged: host-side padding on every dim
    (3072, 256, 128, 128),   # the Embedder's layer-1 shape (d_in x 256)
])
def test_matmul_vs_ref(M, K, N, blk):
    a = _rand((M, K), seed=1, scale=0.5)
    b = _rand((K, N), seed=2, scale=0.5)
    out = ops.matmul(a, b, impl="pallas_interpret", blk_m=blk, blk_n=blk,
                     blk_k=blk)
    np.testing.assert_allclose(out, ref.matmul(a, b), atol=1e-4, rtol=1e-4)


def test_matmul_small_blocks_accumulate():
    """k-loop accumulation across many blocks stays exact vs one block."""
    a = _rand((16, 1024), seed=3)
    b = _rand((1024, 128), seed=4)
    small = ops.matmul(a, b, impl="pallas_interpret", blk_k=128)
    one = ops.matmul(a, b, impl="pallas_interpret", blk_k=1024)
    np.testing.assert_allclose(small, one, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(small, ref.matmul(a, b), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("M,K,N,with_bias,epi", [
    (16, 256, 128, True, "tanh"),     # the fused MLP-layer shape class
    (13, 200, 37, True, "tanh"),      # ragged: epilogue on padded blocks
    (16, 256, 128, True, "none"),     # bias only
    (16, 256, 128, False, "tanh"),    # tanh only
])
def test_matmul_epilogue_vs_ref(M, K, N, with_bias, epi):
    """Fused epilogue == tanh(ref.matmul(a, b) + bias) elementwise."""
    a = _rand((M, K), seed=1, scale=0.3)
    b = _rand((K, N), seed=2, scale=0.3)
    bias = _rand((N,), seed=3) if with_bias else None
    want = ref.matmul(a, b).astype(jnp.float32)
    if bias is not None:
        want = want + bias
    if epi == "tanh":
        want = jnp.tanh(want)
    for impl in ("pallas_interpret", "xla"):
        out = ops.matmul(a, b, bias=bias, epilogue=epi, impl=impl,
                         blk_m=8, blk_n=128, blk_k=128)
        np.testing.assert_allclose(out, want, atol=1e-5, rtol=1e-5)


def test_matmul_epilogue_applied_once_across_k_blocks():
    """The epilogue must fire only on the last k step: many k blocks
    and one k block agree exactly."""
    a = _rand((8, 512), seed=5, scale=0.2)
    b = _rand((512, 128), seed=6, scale=0.2)
    bias = _rand((128,), seed=7)
    many = ops.matmul(a, b, bias=bias, epilogue="tanh",
                      impl="pallas_interpret", blk_m=8, blk_n=128, blk_k=128)
    one = ops.matmul(a, b, bias=bias, epilogue="tanh",
                     impl="pallas_interpret", blk_m=8, blk_n=128, blk_k=512)
    np.testing.assert_allclose(many, one, atol=1e-5, rtol=1e-5)
