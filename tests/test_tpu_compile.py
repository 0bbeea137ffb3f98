"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Each test compiles one kernel, through the same ``ops``/``preprocess``
entry points the program calls, for a described ``v5e:2x2`` topology
(no chip attached). The chip's Mosaic compiler refuses layouts that
interpret mode accepts — a rank-1 SMEM block, an unaligned slice, a
uint8 -> float32 cast, more VMEM than a kernel may use — so these
guard what the CPU tests cannot. Every compile must produce a program
holding a ``tpu_custom_call``: the kernel itself, not a fallback.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.preprocess import device

FRAME_H, FRAME_W = 216, 384           # data.video.VideoStream's frame


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means no topology
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A described-topology compile cannot be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _fused_identify(spec):
    # FusedIdentifier's folded layer: crop pixels (48*48*3) -> hidden 256
    return (lambda a, w, b: ops.matmul(a, w, bias=b, epilogue="tanh",
                                       impl="pallas"),
            spec((8, 6912), jnp.float32), spec((6912, 256), jnp.float32),
            spec((256,), jnp.float32))


def _decode_attention(spec):
    # granite-moe-3b-a800m: 24 query heads over 8 KV heads, head_dim 64
    return (lambda q, k, v, n: ops.decode_attention(q, k, v, kv_len=n,
                                                    impl="pallas"),
            spec((4, 1, 24, 64), jnp.bfloat16),
            spec((4, 512, 8, 64), jnp.bfloat16),
            spec((4, 512, 8, 64), jnp.bfloat16), spec((4,), jnp.int32))


def _flash_attention(spec):
    return (lambda q, k, v: ops.attention(q, k, v, causal=True,
                                          impl="pallas"),
            spec((1, 128, 24, 64), jnp.bfloat16),
            spec((1, 128, 8, 64), jnp.bfloat16),
            spec((1, 128, 8, 64), jnp.bfloat16))


def _letterbox(spec):
    return (lambda f: device.letterbox_normalize(
        f, FRAME_H // 2, FRAME_W // 2, scale=np.ones(3, np.float32),
        offset=np.zeros(3, np.float32), impl="pallas"),
        spec((1, FRAME_H, FRAME_W, 3), jnp.uint8))


def _yuv_to_rgb(spec):
    return (lambda y: device.yuv_to_rgb(y, impl="pallas"),
            spec((1, 3, FRAME_H, FRAME_W), jnp.uint8))


def _iou(spec):
    # DetectPostConfig.max_candidates boxes
    return (lambda b: device.iou_matrix(b, impl="pallas"),
            spec((32, 4), jnp.float32))


KERNELS = {"fused_identify": _fused_identify,
           "decode_attention": _decode_attention,
           "flash_attention": _flash_attention,
           "letterbox": _letterbox,
           "yuv_to_rgb": _yuv_to_rgb,
           "iou": _iou}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, *args = KERNELS[name](spec)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
