"""Bilinear resize Pallas TPU kernel — the pre-processing hot-spot.

The paper measures frame/face resizing at 17.8% of Face Recognition's
end-to-end compute cycles and calls out image pre-processing as an
acceleration target [its ref 62]; on a TPU-resident pipeline the resize
belongs on-device so decoded frames stream HBM->VMEM once.

TPU adaptation: separable bilinear as two dense matmuls — out = Ry @ img
@ Rx^T, with Ry (out_h, in_h) and Rx (out_w, in_w) banded interpolation
matrices built host-side. Gather-style per-pixel addressing is hostile to
the VPU (strided lane access), while the MXU eats 128x128 matmuls; at
typical frame sizes the 2x|rows| nonzeros make the matmul form both
simpler and faster than emulated gathers. The kernel tiles (channel-major)
images over a (batch*channel, out-rows) grid.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.roofline import hw


def _interp_matrix(out_n: int, in_n: int) -> np.ndarray:
    """Rows are bilinear weights (align_corners=False)."""
    c = (np.arange(out_n) + 0.5) * (in_n / out_n) - 0.5
    c = np.clip(c, 0.0, in_n - 1.0)
    lo = np.floor(c).astype(np.int32)
    hi = np.minimum(lo + 1, in_n - 1)
    frac = (c - lo).astype(np.float32)
    m = np.zeros((out_n, in_n), np.float32)
    m[np.arange(out_n), lo] += 1.0 - frac
    m[np.arange(out_n), hi] += frac
    return m


def plane_vmem_bytes(H: int, W: int, blk: int, out_w: int) -> int:
    """VMEM one grid step holds: the whole (H, W) input plane, a (blk, H)
    row-operator block, the (out_w, W) column operator and a (blk, out_w)
    output block, double-buffered in float32."""
    return (H * W + blk * H + out_w * W + blk * out_w) * 4 * 2


def plane_compiler_params(H: int, W: int, blk: int,
                          out_w: int) -> pltpu.CompilerParams | None:
    """A full-HD plane outgrows Mosaic's default scoped VMEM whatever the
    row block is, so ask for what the blocks need (with a quarter of
    headroom for the intermediate) when that is more."""
    need = plane_vmem_bytes(H, W, blk, out_w)
    if need <= hw.SCOPED_VMEM_BYTES:
        return None
    return pltpu.CompilerParams(
        vmem_limit_bytes=min(need + need // 4, hw.VMEM_BYTES * 3 // 4))


def _kernel(img_ref, ry_ref, rx_ref, o_ref):
    img = img_ref[0].astype(jnp.float32)          # (H, W)
    ry = ry_ref[...]                               # (blk_oh, H)
    rx = rx_ref[...]                               # (out_w, W)
    tmp = jax.lax.dot(ry, img)                     # (blk_oh, W)
    o_ref[0] = jax.lax.dot(
        tmp, rx.T).astype(o_ref.dtype)             # (blk_oh, out_w)


def resize_bilinear(img: jax.Array, out_h: int, out_w: int, *,
                    blk_oh: int = 128, interpret: bool = False) -> jax.Array:
    """img: (..., H, W, C) -> (..., out_h, out_w, C)."""
    *lead, H, W, C = img.shape
    x = img.reshape((-1, H, W, C)).transpose(0, 3, 1, 2)   # (N*C planes)
    NB = x.shape[0] * C
    x = x.reshape(NB, H, W)
    ry = jnp.asarray(_interp_matrix(out_h, H))
    rx = jnp.asarray(_interp_matrix(out_w, W))
    blk = min(blk_oh, out_h)
    pad = (-out_h) % blk
    if pad:
        ry = jnp.pad(ry, ((0, pad), (0, 0)))
    n_blocks = (out_h + pad) // blk

    out = pl.pallas_call(
        _kernel,
        grid=(NB, n_blocks),
        in_specs=[
            pl.BlockSpec((1, H, W), lambda n, i: (n, 0, 0)),
            pl.BlockSpec((blk, H), lambda n, i: (i, 0)),
            pl.BlockSpec((out_w, W), lambda n, i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk, out_w), lambda n, i: (n, i, 0)),
        out_shape=jax.ShapeDtypeStruct((NB, out_h + pad, out_w), img.dtype),
        compiler_params=plane_compiler_params(H, W, blk, out_w),
        interpret=interpret,
    )(x, ry, rx)
    out = out[:, :out_h]
    out = out.reshape(-1, C, out_h, out_w).transpose(0, 2, 3, 1)
    return out.reshape((*lead, out_h, out_w, C))
