"""Pallas TPU kernels for the pre/post-processing tax hot-spots.

The paper's §4.3 CPU breakdown charges 17.8% of Face Recognition's
cycles to resizing and a further slice to tensor preparation — work
that stays on the host after the AI is accelerated. These kernels move
the three dense pre/post stages onto the accelerator:

  * :func:`yuv_to_rgb` — frame decode-emulation: the per-pixel 3x3
    color transform from planar 4:4:4 YUV (what the camera/codec
    ships) to the RGB the detector consumes. Pure VPU element-wise
    work, one plane triple per grid step.
  * :func:`letterbox_normalize` — aspect-preserving resize + center
    pad + per-channel affine normalization fused into ONE program.
    Like :mod:`repro.kernels.resize`, the separable bilinear runs as
    two MXU matmuls (``Ly @ img @ Lx^T`` with letterbox-embedded
    operators); the normalization and pad fill run on the accumulator
    while it is still in VMEM, so the frame crosses HBM exactly once.
  * :func:`iou_matrix` — the O(N^2) half of greedy NMS: pairwise IoU
    over component-major boxes, row-blocked over the grid. The greedy
    suppression scan itself is tiny and sequential and stays in the
    surrounding jitted program (:mod:`repro.preprocess.device`).

Tests run every kernel with ``interpret=True`` on the CPU; the program
runs them compiled on the TPU.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.resize import plane_compiler_params


# --------------------------------------------------------------------------
# Planar YUV -> RGB (decode-emulation)
# --------------------------------------------------------------------------

def _as_f32(x):
    # Mosaic has no direct uint8 -> float32 cast; via int32 is exact
    if x.dtype == jnp.uint8:
        x = x.astype(jnp.int32)
    return x.astype(jnp.float32)


def _yuv_kernel(yuv_ref, o_ref):
    x = _as_f32(yuv_ref[0])                       # (3, H, W)
    y = x[0]
    u = x[1] - 128.0
    v = x[2] - 128.0
    r = y + 1.402 * v
    g = y - 0.344136 * u - 0.714136 * v
    b = y + 1.772 * u
    rgb = jnp.stack([r, g, b], axis=0)            # planar (3, H, W)
    o_ref[0] = jnp.clip(jnp.round(rgb), 0.0, 255.0).astype(
        jnp.int32).astype(o_ref.dtype)


_YUV_BAND_ROWS = 64


def yuv_to_rgb(yuv: jax.Array, *, interpret: bool = False) -> jax.Array:
    """(B, 3, H, W) planar uint8 -> (B, H, W, 3) uint8 (BT.601 full).

    The grid walks 64-row bands (a whole full-HD frame's float32 planes
    outgrow VMEM; the last band may overhang and its extra rows are
    dropped). The kernel writes planar RGB: an interleaved (H, W, 3)
    block puts 3 on the 128-wide lane axis, which pads a frame past
    VMEM. XLA interleaves the planes after the call.
    """
    B, _, H, W = yuv.shape
    blk = min(_YUV_BAND_ROWS, H)
    band = pl.BlockSpec((1, 3, blk, W), lambda b, i: (b, 0, i, 0))
    planar = pl.pallas_call(
        _yuv_kernel,
        grid=(B, pl.cdiv(H, blk)),
        in_specs=[band],
        out_specs=band,
        out_shape=jax.ShapeDtypeStruct((B, 3, H, W), jnp.uint8),
        interpret=interpret,
    )(yuv)
    return planar.transpose(0, 2, 3, 1)


# --------------------------------------------------------------------------
# Fused letterbox resize + normalization
# --------------------------------------------------------------------------

def _letterbox_kernel(blk: int, top: int, ch: int, left: int, cw: int,
                      pad_value: float,
                      img_ref, ly_ref, lx_ref, sb_ref, o_ref):
    img = _as_f32(img_ref[0])                     # (H, W)
    hi = jax.lax.Precision.HIGHEST                # float32 on the MXU
    t = jax.lax.dot(ly_ref[...], img, precision=hi)         # (blk, W)
    t = jax.lax.dot(t, lx_ref[...].T, precision=hi)         # (blk, out_w)
    out_w = t.shape[1]
    i = pl.program_id(1)
    rows = i * blk + jax.lax.broadcasted_iota(jnp.int32, (blk, out_w), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (blk, out_w), 1)
    inside = ((rows >= top) & (rows < top + ch)
              & (cols >= left) & (cols < left + cw))
    n = pl.program_id(0)
    norm = t * sb_ref[n, 0] + sb_ref[n, 1]
    o_ref[0] = jnp.where(inside, norm,
                         jnp.float32(pad_value)).astype(o_ref.dtype)


def letterbox_normalize(img: jax.Array, ly: jax.Array, lx: jax.Array,
                        sb: jax.Array, geometry: tuple[int, int, int, int],
                        *, pad_value: float = 0.0, blk_oh: int = 128,
                        interpret: bool = False) -> jax.Array:
    """Fused letterbox+normalize over channel-major planes.

    ``img``: (NB, H, W) planes (batch*channel, channel fastest);
    ``ly``/``lx``: letterbox-embedded interpolation operators
    (out_h, H)/(out_w, W); ``sb``: (NB, 2) per-plane [scale, offset]
    (channel-dependent normalization in plane order); ``geometry``:
    (content_h, content_w, top, left) from
    :func:`repro.preprocess.host.letterbox_geometry`. Returns
    (NB, out_h, out_w) float32.
    """
    NB, H, W = img.shape
    out_h, out_w = ly.shape[0], lx.shape[0]
    ch, cw, top, left = geometry
    blk = min(blk_oh, out_h)
    pad = (-out_h) % blk
    if pad:
        ly = jnp.pad(ly, ((0, pad), (0, 0)))
    n_blocks = (out_h + pad) // blk
    kernel = functools.partial(_letterbox_kernel, blk, top, ch, left, cw,
                               pad_value)
    out = pl.pallas_call(
        kernel,
        grid=(NB, n_blocks),
        in_specs=[
            pl.BlockSpec((1, H, W), lambda n, i: (n, 0, 0)),
            pl.BlockSpec((blk, H), lambda n, i: (i, 0)),
            pl.BlockSpec((out_w, W), lambda n, i: (0, 0)),
            # whole (NB, 2) table in SMEM, row picked by program_id
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, blk, out_w), lambda n, i: (n, i, 0)),
        out_shape=jax.ShapeDtypeStruct((NB, out_h + pad, out_w),
                                       jnp.float32),
        compiler_params=plane_compiler_params(H, W, blk, out_w),
        interpret=interpret,
    )(img, ly, lx, sb)
    return out[:, :out_h]


# --------------------------------------------------------------------------
# Pairwise IoU (the dense half of NMS)
# --------------------------------------------------------------------------

def _iou_kernel(a_ref, b_ref, o_ref):
    a = a_ref[...].astype(jnp.float32)            # (4, blk)  row block
    b = b_ref[...].astype(jnp.float32)            # (4, N)    all boxes
    ay0, ax0, ay1, ax1 = (a[j][:, None] for j in range(4))
    by0, bx0, by1, bx1 = (b[j][None, :] for j in range(4))
    area_a = (ay1 - ay0) * (ax1 - ax0)
    area_b = (by1 - by0) * (bx1 - bx0)
    ih = jnp.maximum(0.0, jnp.minimum(ay1, by1) - jnp.maximum(ay0, by0))
    iw = jnp.maximum(0.0, jnp.minimum(ax1, bx1) - jnp.maximum(ax0, bx0))
    inter = ih * iw
    union = area_a + area_b - inter
    o_ref[...] = inter / jnp.maximum(union, 1e-12)


def iou_matrix(boxes_t: jax.Array, *, blk_n: int = 128,
               interpret: bool = False) -> jax.Array:
    """(4, N) component-major float32 boxes -> (N, N) pairwise IoU."""
    _, N = boxes_t.shape
    blk = min(blk_n, N)
    pad = (-N) % blk
    if pad:
        boxes_t = jnp.pad(boxes_t, ((0, 0), (0, pad)))
    Np = N + pad
    out = pl.pallas_call(
        _iou_kernel,
        grid=(Np // blk,),
        in_specs=[
            pl.BlockSpec((4, blk), lambda i: (0, i)),
            pl.BlockSpec((4, Np), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((blk, Np), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Np, Np), jnp.float32),
        interpret=interpret,
    )(boxes_t, boxes_t)
    return out[:N, :N]
