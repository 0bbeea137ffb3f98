"""Pre/post-processing subsystem: host/device parity (NMS bit-identical,
decode/letterbox numerics), letterbox invariants, five-way tax
attribution, and the normalization-ownership contract."""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:      # deterministic single-example shim
    from hypothesis_fallback import given, settings, st

from repro.core import facerec, taxmeter
from repro.core.events import FIVE_WAY, EventLog, five_way_fractions
from repro.preprocess import NormSpec, PreprocessStage
from repro.preprocess import device as pre_device
from repro.preprocess import host as pre_host


# ---- NMS: host/device parity ----------------------------------------------

def _random_boxes(rng, n):
    cy, cx = rng.uniform(0, 40, n), rng.uniform(0, 40, n)
    h, w = rng.uniform(1, 8, n), rng.uniform(1, 8, n)
    boxes = np.stack([cy - h, cx - w, cy + h, cx + w], 1).astype(np.float32)
    return boxes, rng.uniform(0, 100, n).astype(np.float32)


@pytest.mark.parametrize("n", [1, 2, 7, 31, 40])
def test_nms_host_device_bit_identical(n):
    """Same boxes, same order — the keep DECISIONS must agree bitwise,
    and the gathered boxes to atol 1e-5 (they are exact gathers)."""
    rng = np.random.default_rng(n)
    boxes, scores = _random_boxes(rng, n)
    kw = dict(iou_thresh=0.3, score_thresh=25.0, max_out=10)
    keep_h = pre_host.nms(boxes, scores, **kw)
    keep_d = pre_device.nms(boxes, scores, **kw)
    assert keep_h == keep_d
    np.testing.assert_allclose(boxes[keep_h], boxes[keep_d], atol=1e-5)


def test_nms_edge_cases():
    assert pre_device.nms(np.zeros((0, 4)), np.zeros((0,))) == []
    assert pre_host.nms(np.zeros((0, 4)), np.zeros((0,))) == []
    # exact duplicates: IoU 1 suppresses, stable tie-break keeps the
    # lower index — on both substrates
    boxes = np.array([[0, 0, 4, 4], [0, 0, 4, 4], [10, 10, 14, 14]],
                     np.float32)
    scores = np.array([5.0, 5.0, 1.0], np.float32)
    for impl in (pre_host.nms, pre_device.nms):
        assert impl(boxes, scores, iou_thresh=0.5) == [0, 2]


def test_nms_max_out_and_threshold():
    rng = np.random.default_rng(0)
    boxes, scores = _random_boxes(rng, 25)
    got = pre_host.nms(boxes, scores, iou_thresh=0.9, score_thresh=50.0,
                       max_out=3)
    assert len(got) == 3
    assert all(scores[i] >= 50.0 for i in got)
    # best-first order
    assert list(np.asarray([scores[i] for i in got])) == \
        sorted((scores[i] for i in got), reverse=True)


def test_postprocess_stage_parity_and_contract():
    """Heatmap -> centers: host and device placements agree exactly and
    respect the max_faces cap."""
    rng = np.random.default_rng(3)
    hms = rng.normal(30, 8, (5, 13, 24)).astype(np.float32)
    for b in range(5):
        for _ in range(b):
            y, x = int(rng.integers(1, 12)), int(rng.integers(1, 23))
            hms[b, y, x] += 120.0
    got_h = PreprocessStage("host").postprocess(hms, facerec.DETECT_POOL)
    got_d = PreprocessStage("device").postprocess(hms, facerec.DETECT_POOL)
    assert got_h == got_d
    assert all(len(c) <= PreprocessStage("host").post.max_faces
               for c in got_h)
    assert any(got_h)                      # the spiked frames detect


# ---- decode ----------------------------------------------------------------

def test_yuv_roundtrip_and_parity():
    rng = np.random.default_rng(1)
    rgb = rng.integers(0, 256, (3, 20, 24, 3), np.uint8)
    yuv = pre_host.rgb_to_yuv(rgb)
    assert yuv.shape == (3, 3, 20, 24)
    back = pre_host.yuv_to_rgb(yuv)
    # uint8 quantization through the color transform: within ±2
    assert np.abs(back.astype(int) - rgb.astype(int)).max() <= 2
    dev = PreprocessStage("device").decode(yuv)
    np.testing.assert_array_equal(dev, back)


# ---- letterbox -------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(st.integers(16, 64), st.integers(16, 64))
def test_letterbox_shape_and_aspect_invariants(out_h, out_w):
    """Output shape is the target; the content window preserves the
    input aspect via the shared scale r = min(out/in); padding carries
    exactly the pad value; the binding dimension is filled."""
    H, W = 24, 40
    rng = np.random.default_rng(0)
    img = rng.uniform(1.0, 255.0, (2, H, W, 3)).astype(np.float32)
    pad = -7.5
    out = pre_host.letterbox_normalize(
        img, out_h, out_w, scale=np.ones(3, np.float32),
        offset=np.zeros(3, np.float32), pad_value=pad)
    assert out.shape == (2, out_h, out_w, 3) and out.dtype == np.float32
    ch, cw, top, left = pre_host.letterbox_geometry(H, W, out_h, out_w)
    r = min(out_h / H, out_w / W)
    assert abs(ch - H * r) <= 0.5 or ch in (1, out_h)
    assert abs(cw - W * r) <= 0.5 or cw in (1, out_w)
    assert ch == out_h or cw == out_w       # content fills one dim
    mask = np.zeros((out_h, out_w), bool)
    mask[top:top + ch, left:left + cw] = True
    assert np.all(out[:, ~mask] == pad)
    assert np.all(out[:, mask] >= 0.0)      # content came from the image


def test_letterbox_identity_roundtrip():
    """Same-size target, identity norm: letterbox IS the identity (the
    interpolation operator at equal sizes is the identity matrix)."""
    rng = np.random.default_rng(2)
    img = rng.uniform(0, 255, (2, 18, 30, 3)).astype(np.float32)
    out = pre_host.letterbox_normalize(
        img, 18, 30, scale=np.ones(3, np.float32),
        offset=np.zeros(3, np.float32))
    np.testing.assert_allclose(out, img, atol=1e-4)


def test_letterbox_host_device_parity():
    rng = np.random.default_rng(4)
    img = rng.uniform(0, 255, (2, 20, 34, 3)).astype(np.float32)
    kw = dict(scale=np.float32([1 / 255] * 3),
              offset=np.float32([-0.5, 0.0, 0.25]), pad_value=0.125)
    got_h = pre_host.letterbox_normalize(img, 28, 28, **kw)
    import jax.numpy as jnp
    got_d = np.asarray(pre_device.letterbox_normalize(
        jnp.asarray(img), 28, 28, **kw))
    np.testing.assert_allclose(got_h, got_d, atol=1e-4)


# ---- Pallas preprocess kernels (interpret mode) vs the baselines ----------

@pytest.mark.parametrize("H", [20, 70])   # 70: two 64-row bands, one short
def test_yuv_kernel_matches_host(H):
    import jax.numpy as jnp
    yuv = np.random.default_rng(5).integers(0, 256, (2, 3, H, 24), np.uint8)
    got = np.asarray(pre_device.yuv_to_rgb(jnp.asarray(yuv),
                                           impl="pallas_interpret"))
    ref = pre_host.yuv_to_rgb(yuv)
    # f32 elementwise vs the host's f32 matmul: a .5 tie may round apart
    assert got.shape == ref.shape and got.dtype == np.uint8
    assert np.abs(got.astype(int) - ref.astype(int)).max() <= 1


@pytest.mark.parametrize("dtype", [np.uint8, np.float32])
def test_letterbox_kernel_matches_host(dtype):
    """Per-channel scale/offset rows come from the SMEM table by plane
    index, so two frames x three channels must each get their own."""
    import jax.numpy as jnp
    img = np.random.default_rng(6).uniform(0, 255, (2, 20, 34, 3))
    img = img.astype(dtype)
    kw = dict(scale=np.float32([1 / 255, 2 / 255, 3 / 255]),
              offset=np.float32([-0.5, 0.0, 0.25]), pad_value=0.125)
    got = np.asarray(pre_device.letterbox_normalize(
        jnp.asarray(img), 28, 28, impl="pallas_interpret", **kw))
    ref = pre_host.letterbox_normalize(img.astype(np.float32), 28, 28, **kw)
    np.testing.assert_allclose(got, ref, atol=1e-4)


def test_iou_kernel_matches_xla():
    import jax.numpy as jnp
    boxes, _ = _random_boxes(np.random.default_rng(7), 37)
    got = pre_device.iou_matrix(jnp.asarray(boxes), impl="pallas_interpret")
    ref = pre_device.iou_matrix(jnp.asarray(boxes), impl="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-6)


# ---- five-way attribution --------------------------------------------------

def test_event_log_five_way_sums_to_one():
    log = EventLog()
    log.log(0, "pre_decode", 0.00, 0.02)
    log.log(0, "ingest", 0.02, 0.03)
    log.log(0, "detect", 0.03, 0.10)
    log.log(0, "post_nms", 0.10, 0.12)
    log.log(0, "wait", 0.12, 0.20)
    log.log(0, "identify", 0.20, 0.30)
    log.log_transfer(0, "h2d", 1024, "detect", 0.30, 0.32)
    fr = log.five_way(facerec.stage_category)
    assert set(fr) == set(FIVE_WAY)
    assert sum(fr.values()) == pytest.approx(1.0, abs=1e-12)
    total = 0.32
    assert fr["pre"] == pytest.approx(0.03 / total)
    assert fr["ai"] == pytest.approx(0.17 / total)
    assert fr["post"] == pytest.approx(0.02 / total)
    assert fr["queue"] == pytest.approx(0.08 / total)
    assert fr["transfer"] == pytest.approx(0.02 / total)
    tax = log.ai_tax(ai_stages={"detect", "identify"},
                     category_of=facerec.stage_category)
    assert tax["fractions"] == fr
    assert tax["pre_fraction"] == fr["pre"]
    assert tax["post_fraction"] == fr["post"]
    # the sum aggregation shares the same attribution (incl. the
    # transfer-kind override) and accounts every logged second
    sec = log.five_way_seconds(facerec.stage_category)
    assert sum(sec.values()) == pytest.approx(
        sum(ev.duration for ev in log.events))
    assert sec["transfer"] == pytest.approx(0.02)


def test_five_way_rejects_unknown_bucket():
    with pytest.raises(ValueError):
        five_way_fractions({"x": 1.0}, lambda s: "nonsense")


def test_taxed_step_five_way():
    from repro.core.taxmeter import TaxedStep
    import jax.numpy as jnp
    step = TaxedStep(EventLog(), name="s")
    step.run(0, pre=lambda x: x + 1, compute=lambda x: x * 2,
             post=lambda y: y - 1, payload=np.ones((8, 8), np.float32))
    bd = step.breakdown()
    fr = bd["fractions"]
    assert sum(fr.values()) == pytest.approx(1.0)
    assert fr["pre"] > 0 and fr["ai"] > 0 and fr["post"] > 0
    assert bd["pre_fraction"] == fr["pre"]
    assert bd["post_fraction"] == fr["post"]
    for stage, cat in [("s/pre", "pre"), ("s/compute", "ai"),
                       ("s/h2d", "transfer"), ("s/d2h", "transfer"),
                       ("s/post", "post"), ("wait", "queue")]:
        assert taxmeter.taxed_stage_category(stage) == cat


def test_pipeline_five_way_fractions_sum_to_one():
    from repro.core.pipeline import StreamingPipeline
    r = StreamingPipeline(n_frames=10, seed=2, n_identify_workers=1).run()
    fr = r.ai_tax()["fractions"]
    assert sum(fr.values()) == pytest.approx(1.0)
    assert fr["pre"] > 0 and fr["ai"] > 0 and fr["queue"] > 0
    stages = set(r.log.breakdown())
    assert {"pre_decode", "pre_letterbox", "post_nms", "detect"} <= stages


# ---- placement + normalization contracts -----------------------------------

def test_device_placement_logs_transfer_bytes():
    log = EventLog()
    stage = PreprocessStage("device", log=log)
    rng = np.random.default_rng(5)
    yuv = rng.integers(0, 256, (2, 3, 16, 16), np.uint8)
    stage.ingest(yuv, 8, 8, rids=[7, 8])
    tb = log.transfer_bytes(boundary="pre_decode")
    assert tb["h2d"] == yuv.nbytes
    assert tb["d2h"] == 2 * 16 * 16 * 3          # uint8 RGB back
    assert log.transfer_bytes(boundary="pre_letterbox")["total"] > 0
    # host placement logs spans but no crossings
    log2 = EventLog()
    PreprocessStage("host", log=log2).ingest(yuv, 8, 8, rids=[7, 8])
    assert log2.transfer_bytes()["total"] == 0
    assert {"pre_decode", "pre_letterbox"} <= set(log2.breakdown())


def test_fused_identifier_folds_stage_norm():
    """A non-trivial crop norm (mean/std) must give the same identities
    through the host embedder chain and the fused device fold — the
    stage owns the constants, both consumers derive from it."""
    norm = NormSpec(mean=(0.3, 0.2, 0.1), std=(0.5, 0.6, 0.7),
                    to_unit=True)
    emb = facerec.Embedder(norm=norm)
    rng = np.random.default_rng(6)
    gal_thumbs = rng.uniform(0, 255, (5, facerec.THUMB, facerec.THUMB, 3))
    gal = {f"p{i}": e
           for i, e in enumerate(emb.embed_batch(gal_thumbs
                                                 .astype(np.float32)))}
    clf = facerec.Classifier(gal)
    fused = facerec.FusedIdentifier(emb, clf)
    assert fused.b1 is not None              # offset fold engaged
    crops = rng.integers(0, 256, (3, facerec.CROP_SIZE,
                                  facerec.CROP_SIZE, 3), np.uint8)
    thumbs = facerec.crop_thumbnails_batch(
        [c for c in crops], [[(facerec.CROP_SIZE // 2,
                               facerec.CROP_SIZE // 2)]] * 3)
    flat = np.stack([t for ts in thumbs for t in ts])
    want = clf.identify_batch(emb.embed_batch(flat))
    got = fused.identify_crops(crops)
    for (n1, s1), (n2, s2) in zip(want, got):
        assert n1 == n2
        assert s1 == pytest.approx(s2, abs=1e-3)


def test_build_identify_stack_carries_preprocess():
    stack = facerec.build_identify_stack(seed=0, gallery_size=4,
                                         placement="device")
    assert isinstance(stack.preprocess, PreprocessStage)
    assert stack.preprocess.placement == "device"
    assert stack.embedder.norm == stack.preprocess.crop_norm
    assert stack.fused is not None and stack.fused.b1 is None


def test_pipeline_device_placement_smoke():
    from repro.core.pipeline import StreamingPipeline
    r = StreamingPipeline(n_frames=8, seed=0, n_identify_workers=1,
                          placement="device").run()
    assert len(r.identities) == r.detected
    assert r.recall >= 0.6
    # the offloaded pre/post stages logged their boundary bytes
    assert r.log.transfer_bytes(boundary="pre_letterbox")["total"] > 0
