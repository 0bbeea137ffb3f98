"""Device-side pre/post-processing programs (jitted; Pallas-backed).

Each entry point mirrors a host baseline in :mod:`repro.preprocess.host`
and dispatches on the repo's kernel-impl convention
(:mod:`repro.kernels.ops`): ``xla`` lowers anywhere (the default on this
CPU container), ``pallas``/``pallas_interpret`` route the dense parts
through :mod:`repro.kernels.preproc`. The greedy NMS scan is sequential
and tiny, so it stays a ``fori_loop`` inside the jitted program on every
impl — only the O(N^2) IoU matrix changes substrate.

Numerics match the host baselines operation-for-operation in float32:
host and device NMS make bit-identical keep decisions (asserted by
``tests/test_preprocess.py`` and ``benchmarks/fig_preprocess_offload``).
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.preprocess import host as _host


def _use_pallas(impl: ops.Impl | None) -> tuple[bool, bool]:
    impl = ops._resolve(impl)
    return impl in ("pallas", "pallas_interpret"), impl == "pallas_interpret"


# --------------------------------------------------------------------------
# Decode-emulation: planar YUV -> RGB
# --------------------------------------------------------------------------

@jax.jit
def _yuv_to_rgb_xla(yuv):
    x = jnp.moveaxis(yuv, -3, -1).astype(jnp.float32)
    x = x - jnp.asarray([0.0, 128.0, 128.0], jnp.float32)
    # HIGHEST: at the TPU's default precision the bf16-rounded
    # coefficients move pixels by one level against the host baseline
    rgb = jnp.matmul(x, jnp.asarray(_host._YUV_TO_RGB.T),
                     precision=jax.lax.Precision.HIGHEST)
    return jnp.clip(jnp.round(rgb), 0.0, 255.0).astype(jnp.uint8)


def yuv_to_rgb(yuv: jax.Array, *, impl: ops.Impl | None = None) -> jax.Array:
    """(B, 3, H, W) planar uint8 -> (B, H, W, 3) uint8, on device."""
    pallas, interp = _use_pallas(impl)
    if pallas:
        from repro.kernels import preproc
        return preproc.yuv_to_rgb(yuv, interpret=interp)
    return _yuv_to_rgb_xla(yuv)


# --------------------------------------------------------------------------
# Fused letterbox resize + normalization
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def _letterbox_operators(in_h: int, in_w: int, out_h: int, out_w: int):
    """Device-resident (ly, lx, pad-mask) per geometry: the operator
    build + host->device upload happens once, not per taxed call."""
    ly, lx = _host.embedded_interp_matrices(in_h, in_w, out_h, out_w)
    mask = _host._content_mask(in_h, in_w, out_h, out_w)
    return jnp.asarray(ly), jnp.asarray(lx), jnp.asarray(mask)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _letterbox_xla(img, ly, lx, sb_scale, sb_offset):
    # one program: resize (two contractions), affine, pad fill — the
    # mask is implicit in the zero rows of the embedded operators, so
    # pad cells come out as 0 * scale + offset_pad handled below
    t = jnp.einsum("oh,bhwc,pw->bcop", ly, img.astype(jnp.float32), lx,
                   precision=jax.lax.Precision.HIGHEST)
    s = jnp.asarray(sb_scale, jnp.float32)[None, :, None, None]
    o = jnp.asarray(sb_offset, jnp.float32)[None, :, None, None]
    return jnp.moveaxis(t * s + o, 1, -1)


def letterbox_normalize(img: jax.Array, out_h: int, out_w: int, *,
                        scale, offset, pad_value: float = 0.0,
                        impl: ops.Impl | None = None) -> jax.Array:
    """(B, H, W, C) -> (B, out_h, out_w, C) float32, one device program.

    Same semantics as :func:`repro.preprocess.host.letterbox_normalize`:
    aspect-preserving bilinear into a centered window, per-channel
    ``x * scale + offset`` on the content, ``pad_value`` outside.
    """
    B, H, W, C = img.shape
    ly, lx, mask = _letterbox_operators(H, W, out_h, out_w)
    pallas, interp = _use_pallas(impl)
    if pallas:
        from repro.kernels import preproc
        geom = _host.letterbox_geometry(H, W, out_h, out_w)
        planes = img.transpose(0, 3, 1, 2).reshape(B * C, H, W)
        sb = jnp.tile(jnp.stack([jnp.asarray(scale, jnp.float32),
                                 jnp.asarray(offset, jnp.float32)], axis=1),
                      (B, 1))
        out = preproc.letterbox_normalize(
            planes, ly, lx, sb, geom, pad_value=pad_value,
            interpret=interp)
        return out.reshape(B, C, out_h, out_w).transpose(0, 2, 3, 1)
    out = _letterbox_xla(img, ly, lx,
                         tuple(np.asarray(scale, np.float32).tolist()),
                         tuple(np.asarray(offset, np.float32).tolist()))
    return jnp.where(mask[None, :, :, None], out, jnp.float32(pad_value))


# --------------------------------------------------------------------------
# Detection post-processing: threshold + greedy IoU NMS
# --------------------------------------------------------------------------

def _iou_matrix_jnp(boxes):
    y0, x0, y1, x1 = boxes[:, 0], boxes[:, 1], boxes[:, 2], boxes[:, 3]
    area = (y1 - y0) * (x1 - x0)
    ih = jnp.maximum(0.0, jnp.minimum(y1[:, None], y1[None, :])
                     - jnp.maximum(y0[:, None], y0[None, :]))
    iw = jnp.maximum(0.0, jnp.minimum(x1[:, None], x1[None, :])
                     - jnp.maximum(x0[:, None], x0[None, :]))
    inter = ih * iw
    union = area[:, None] + area[None, :] - inter
    return inter / jnp.maximum(union, 1e-12)


def iou_matrix(boxes: jax.Array, *, impl: ops.Impl | None = None,
               ) -> jax.Array:
    """(N, 4) float32 -> (N, N) pairwise IoU (Pallas on TPU)."""
    pallas, interp = _use_pallas(impl)
    if pallas:
        from repro.kernels import preproc
        return preproc.iou_matrix(boxes.T, interpret=interp)
    return _iou_matrix_jnp(boxes.astype(jnp.float32))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _nms_sorted_jit(iou, alive, iou_thresh, max_out):
    """Greedy scan over the full (padded) candidate length: visiting a
    dead/padded row is a no-op, so the compile is keyed only by the
    pow2 bucket + thresholds — one program per bucket, not per N."""
    N = alive.shape[0]
    idx = jnp.arange(N)
    thr = jnp.float32(iou_thresh)

    def body(i, state):
        alive, keep, count = state
        sel = alive[i] & (count < max_out)
        keep = keep.at[i].set(sel)
        count = count + sel.astype(jnp.int32)
        suppress = sel & (idx > i) & (iou[i] > thr)
        return alive & ~suppress, keep, count

    keep0 = jnp.zeros((N,), bool)
    _, keep, _ = jax.lax.fori_loop(0, N, body, (alive, keep0, jnp.int32(0)))
    return keep


def nms(boxes: np.ndarray, scores: np.ndarray, *, iou_thresh: float = 0.5,
        score_thresh: float = 0.0, max_out: int | None = None,
        impl: ops.Impl | None = None) -> list[int]:
    """Device-side greedy NMS; same contract as ``host.nms``.

    Sorting, thresholding and the suppression scan run in one jitted
    program over the (padded) candidate set; only the kept indices
    come back. Keep decisions are bit-identical to the host baseline.
    """
    boxes = np.asarray(boxes, np.float32).reshape(-1, 4)
    scores = np.asarray(scores, np.float32).reshape(-1)
    N = len(scores)
    if N == 0:
        return []
    # pow2 bucket (like facerec batch padding) so jit retraces stay
    # bounded across battery sizes; pads sort last via -inf scores and
    # are masked out of `alive`, so the scan ignores them
    Np = 1 << (N - 1).bit_length()
    cap = Np if max_out is None else max_out
    boxes_p = np.zeros((Np, 4), np.float32)
    boxes_p[:N] = boxes
    scores_p = np.full((Np,), -np.inf, np.float32)
    scores_p[:N] = scores
    order = jnp.argsort(-jnp.asarray(scores_p), stable=True)
    sboxes = jnp.asarray(boxes_p)[order]
    salive = (jnp.asarray(scores_p)[order] >= jnp.float32(score_thresh)) \
        & (order < N)
    iou = iou_matrix(sboxes, impl=impl)
    keep = _nms_sorted_jit(iou, salive, float(iou_thresh), cap)
    keep = np.asarray(keep)
    order = np.asarray(order)
    return [int(order[i]) for i in range(Np) if keep[i]]


# --------------------------------------------------------------------------
# Batched heatmap post-processing (the pipeline's device path)
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _postprocess_heatmaps_jit(hms, k, box_cells, score_thresh, iou_thresh,
                              max_out):
    """(B, Hc, Wc) heatmaps -> top-k boxes + NMS keep mask, on device.

    Per frame: stable descending argsort of the flattened heatmap picks
    the k candidate cells, boxes of ``box_cells`` side are built around
    their centers, and the greedy scan suppresses on IoU. Everything —
    candidate selection included — runs in the one program; only
    (boxes, scores, keep) cross back.
    """
    B, Hc, Wc = hms.shape
    flat = hms.astype(jnp.float32).reshape(B, -1)
    order = jnp.argsort(-flat, axis=1, stable=True)[:, :k]
    scores = jnp.take_along_axis(flat, order, axis=1)
    cy = (order // Wc).astype(jnp.float32) + 0.5
    cx = (order % Wc).astype(jnp.float32) + 0.5
    h = jnp.float32(box_cells / 2.0)
    boxes = jnp.stack([cy - h, cx - h, cy + h, cx + h], axis=-1)

    def one(bx, sc):
        iou = _iou_matrix_jnp(bx)
        alive = sc >= jnp.float32(score_thresh)
        return _nms_sorted_jit(iou, alive, iou_thresh, max_out)

    keep = jax.vmap(one)(boxes, scores)
    return boxes, scores, keep


def postprocess_heatmaps(hms: np.ndarray, *, k: int, box_cells: float,
                         score_thresh: float, iou_thresh: float,
                         max_out: int,
                         ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Batched device post-processing; returns (boxes, scores, keep).

    ``hms``: (B, Hc, Wc). Candidates arrive already score-sorted per
    frame (the argsort IS the NMS visit order), so ``keep[b]`` marks
    survivors in best-first order. Shapes are fixed by ``k``; callers
    gather kept rows host-side. B is padded to its pow2 bucket (all-
    zero heatmaps detect nothing) so ragged micro-batch flushes reuse
    compiled programs instead of paying a mid-run jit inside the taxed
    ``post_nms`` span.
    """
    hms = np.asarray(hms)
    B = hms.shape[0]
    pad = (1 << (B - 1).bit_length()) - B
    if pad:
        hms = np.concatenate(
            [hms, np.zeros((pad, *hms.shape[1:]), hms.dtype)], axis=0)
    boxes, scores, keep = _postprocess_heatmaps_jit(
        jnp.asarray(hms), int(k), float(box_cells), float(score_thresh),
        float(iou_thresh), int(max_out))
    return (np.asarray(boxes)[:B], np.asarray(scores)[:B],
            np.asarray(keep)[:B])
