"""Tiled matmul Pallas TPU kernel — the batched-inference workhorse.

The micro-batched face pipeline turns B per-face MLP calls into one
(B, d_in) @ (d_in, d_out) matmul; this kernel is the on-device form of
that contraction. Classic three-level tiling: the grid iterates
(m, n, k) blocks with k innermost, a float32 VMEM scratch accumulates
partial products across the k dimension, and the MXU sees one
(blk_m, blk_k) @ (blk_k, blk_n) dot per step. Inputs are padded
host-side to block multiples so BlockSpecs stay static; padding is
sliced off after the call.

The kernel also carries a fused epilogue (bias add and/or tanh) applied
to the float32 accumulator on the last k step, so an MLP layer's
activation never round-trips through HBM between the contraction and
the nonlinearity — the embedder's two-matmul MLP uses this to keep its
hidden layer entirely in VMEM.

Block sizes default to autotuned values (see repro.kernels.autotune)
when not given explicitly via :func:`repro.kernels.ops.matmul`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

EPILOGUES = ("none", "tanh")


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _apply_epilogue(acc, bias, epilogue: str):
    """Float32 epilogue on the accumulator (shared by kernel and oracle)."""
    if bias is not None:
        acc = acc + bias.astype(jnp.float32)
    if epilogue == "tanh":
        acc = jnp.tanh(acc)
    return acc


def _kernel(*refs, n_k_blocks: int, epilogue: str, has_bias: bool):
    if has_bias:
        a_ref, b_ref, bias_ref, o_ref, acc_ref = refs
    else:
        a_ref, b_ref, o_ref, acc_ref = refs
        bias_ref = None
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # HIGHEST: a float32 dot on the MXU otherwise rounds its operands
    # to bf16, and the float32 contract (and the oracle) no longer holds
    acc_ref[...] += jax.lax.dot(
        a_ref[...].astype(jnp.float32), b_ref[...].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)

    @pl.when(ki == n_k_blocks - 1)
    def _finish():
        out = _apply_epilogue(
            acc_ref[...], bias_ref[...] if has_bias else None, epilogue)
        o_ref[...] = out.astype(o_ref.dtype)


def matmul(a: jax.Array, b: jax.Array, *, bias: jax.Array | None = None,
           epilogue: str = "none", blk_m: int = 128,
           blk_n: int = 128, blk_k: int = 512,
           interpret: bool = False) -> jax.Array:
    """a: (M, K) @ b: (K, N) -> (M, N); accumulation in float32.

    ``bias`` is an (N,) vector added to the accumulator; ``epilogue``
    in ``EPILOGUES`` optionally applies tanh — both fused into the last
    k step, on-chip.
    """
    assert epilogue in EPILOGUES, epilogue
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    # clamp blocks to the (sublane, lane)-aligned problem size
    blk_m = min(blk_m, _round_up(M, 8))
    blk_n = min(blk_n, _round_up(N, 128))
    blk_k = min(blk_k, _round_up(K, 128))
    Mp, Kp, Np = (_round_up(M, blk_m), _round_up(K, blk_k),
                  _round_up(N, blk_n))
    if (Mp, Kp) != (M, K):
        a = jnp.pad(a, ((0, Mp - M), (0, Kp - K)))
    if (Kp, Np) != (K, N):
        b = jnp.pad(b, ((0, Kp - K), (0, Np - N)))
    n_k = Kp // blk_k

    has_bias = bias is not None
    in_specs = [
        pl.BlockSpec((blk_m, blk_k), lambda i, j, k: (i, k)),
        pl.BlockSpec((blk_k, blk_n), lambda i, j, k: (k, j)),
    ]
    operands = [a, b]
    if has_bias:
        assert bias.shape == (N,), (bias.shape, N)
        operands.append(jnp.pad(bias, (0, Np - N))[None, :])
        in_specs.append(pl.BlockSpec((1, blk_n), lambda i, j, k: (0, j)))

    out = pl.pallas_call(
        functools.partial(_kernel, n_k_blocks=n_k, epilogue=epilogue,
                          has_bias=has_bias),
        grid=(Mp // blk_m, Np // blk_n, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((blk_m, blk_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), a.dtype),
        scratch_shapes=[pltpu.VMEM((blk_m, blk_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)
    return out[:M, :N]
