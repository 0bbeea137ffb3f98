"""Decode attention Pallas TPU kernel (one token vs a long KV cache).

Decode is bandwidth-bound: the kernel streams the cache HBM->VMEM once in
(blk_k x D) tiles and keeps the online-softmax state in VMEM scratch. All
G query heads of a KV group are processed together as the (sublane) rows
of one tile so the MXU sees a (G x D) @ (D x blk_k) matmul per tile
instead of G vector products.

Grid: (batch, kv_heads, kv_blocks). The (B,) ``kv_len`` vector is a
scalar-prefetch operand: it lands in SMEM whole before the grid starts
and each step reads its row's length by ``program_id``. Validity
(kv_len) and sliding-window masks are applied per tile; fully-invalid
tiles are skipped before any VMEM compute via pl.when on the block
index.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

_LANE = 128


def legal_blk_k(blk_k: int, L: int) -> int:
    """Largest KV tile <= ``blk_k`` whose grid tiles ``L`` exactly.

    The kernel's (batch, kv_heads, kv_blocks) grid requires ``L % blk_k
    == 0``; a requested tile (the default, or an autotuned pick keyed on
    a different cache length) is rounded down to the largest divisor of
    ``L`` — preferring lane-aligned (multiple-of-128) tiles so the MXU
    edge stays full — instead of tripping a trace-time assert on cache
    lengths like 768 that the default 512 does not divide.
    """
    b = min(blk_k, L)
    if b <= 0:
        return L
    if L % b == 0:
        return b
    for c in range(b - b % _LANE, 0, -_LANE):
        if L % c == 0:
            return c
    for c in range(b, 0, -1):
        if L % c == 0:
            return c
    return L


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, window: int | None, blk_k: int, n_blocks: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kv_len = len_ref[pl.program_id(0)]
    first_k = ki * blk_k
    run = first_k < kv_len
    if window is not None:
        run &= (ki + 1) * blk_k > kv_len - window

    @pl.when(run)
    def _block():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)                  # (blk_k, D)
        v = v_ref[0, 0].astype(jnp.float32)                  # (blk_k, Dv)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (G, blk_k)
        k_pos = first_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = k_pos < kv_len
        if window is not None:
            valid &= k_pos > kv_len - 1 - window
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(p, v)
        m_scr[...] = m_new

    @pl.when(ki == n_blocks - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def decode_attention(
    q: jax.Array,          # (B, 1, H, D)
    k: jax.Array,          # (B, L, KV, D)
    v: jax.Array,          # (B, L, KV, Dv)
    *,
    kv_len: jax.Array,     # (B,) valid entries
    window: int | None = None,
    scale: float | None = None,
    blk_k: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    B, _, H, D = q.shape
    _, L, KV, Dv = v.shape
    G = H // KV
    scale = (1.0 / D**0.5) if scale is None else scale
    blk_k = legal_blk_k(512 if blk_k is None else blk_k, L)
    n_blocks = L // blk_k

    qt = q.reshape(B, KV, G, D)                 # group-major layout
    kt = k.transpose(0, 2, 1, 3)                # (B, KV, L, D)
    vt = v.transpose(0, 2, 1, 3)

    kern = functools.partial(_kernel, scale=scale, window=window,
                             blk_k=blk_k, n_blocks=n_blocks)
    # index maps take the prefetched kv_len ref as a trailing argument
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, KV, n_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, j, _: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, blk_k, D), lambda b, h, j, _: (b, h, j, 0)),
            pl.BlockSpec((1, 1, blk_k, Dv), lambda b, h, j, _: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, Dv),
                               lambda b, h, j, _: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, Dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, Dv), q.dtype),
        interpret=interpret,
    )(kv_len.astype(jnp.int32), qt, kt, vt)
    return out.reshape(B, 1, H, Dv)
