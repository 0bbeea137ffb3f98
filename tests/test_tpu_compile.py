"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Each test compiles one kernel, through the same ``ops``/``preprocess``
entry points the program calls, for a described ``v5e:2x2`` topology
(no chip attached). The chip's Mosaic compiler refuses layouts that
interpret mode accepts — a rank-1 SMEM block, an unaligned slice, a
uint8 -> float32 cast, more VMEM than a kernel may use — so these
guard what the CPU tests cannot. Every compile must produce a program
holding a ``tpu_custom_call``: the kernel itself, not a fallback.
"""
import re

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


def _instructions(hlo: str):
    """The optimized HLO module's instructions with an array shape, as
    ``{computation: [(name, dtype, dims, layout, opcode, operands,
    called)]}``, and the names of the computations a fusion calls."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head and not line.startswith(" "):
            cur = comps.setdefault(head.group(1), [])
            continue
        m = re.match(r"\s+(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\]"
                     r"(\{[^}]*\})? ([\w\-]+)\(([^)]*)\)", line)
        if cur is not None and m:
            called = re.search(r"calls=%?([\w.\-]+)", line)
            cur.append((m.group(1), m.group(2),
                        tuple(int(d) for d in m.group(3).split(",") if d),
                        m.group(4) or "", m.group(5),
                        re.findall(r"%([\w.\-]+)", m.group(6)),
                        called and called.group(1)))
    fused = set(re.findall(r" fusion\(.*calls=%?([\w.\-]+)", hlo))
    return comps, fused


@pytest.mark.parametrize("head_dim", [64, 128])
def test_decode_step_reads_the_cache_in_place(head_dim, one_chip,
                                              no_persistent_cache):
    """The ragged decode step, compiled as the engine runs it, moves no
    layer's KV block through device memory: no op of the optimized
    program (outside fusions) makes a layer's block or the stacked cache
    in HBM, apart from one copy per cache leaf into the fresh output and
    the in-place writes of one row per slot, and its scratch memory is
    under one layer's cache. A prefetch into the core's vector memory
    (memory space ``S(1)``) is the read the attention makes anyway.
    granite-moe-3b-a800m's block at 4 layers, 8 slots of 512, with its
    head_dim 64 (stored with the sequence axis minor) and with 128
    (stored as laid out). Four layers, not two: at two, a step that
    slices and restacks every layer's block keeps the whole output stack
    in vector memory, and this check could not see it."""
    from math import prod

    from repro.configs import get_config
    from repro.models.model import build_model
    from repro.serve import engine

    slots, cache_len, layers = 8, 512, 4
    cfg = get_config("granite-moe-3b-a800m").replace(n_layers=layers,
                                                     head_dim=head_dim)
    model = build_model(cfg)
    spec = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
    params = jax.tree.map(spec, model.abstract_params(jnp.bfloat16))
    blocks = jax.tree.map(spec, model.abstract_cache(slots, cache_len)["blocks"])
    packed = jax.ShapeDtypeStruct((2, slots), jnp.int32, sharding=one_chip)
    compiled = engine._step_batched_fused.lower(
        model, "xla", params, blocks, packed).compile()

    leaves = jax.tree.leaves(blocks)
    stack = leaves[0].shape                     # (layers, slots, KV, L, D)
    layer_elems = prod(stack[1:])
    comps, fused = _instructions(compiled.as_text())
    shapes = {i[0]: i[2] for ins in comps.values() for i in ins}
    roots = {c: ins[-1] for c, ins in comps.items() if ins}
    moves, output_copies = [], []
    for comp, ins in comps.items():
        if comp in fused:
            continue        # inside a fusion: nothing is materialized
        for name, dtype, dims, layout, op, operands, called in ins:
            if (dtype != "bf16" or cache_len not in dims
                    or prod(dims) not in (layer_elems, prod(stack))
                    or re.search(r"S\([1-9]\)", layout)
                    or op in ("parameter", "get-tuple-element", "bitcast")):
                continue
            if op in ("copy", "copy-done") and prod(dims) == prod(stack):
                output_copies.append(name)
                continue
            update = None
            if op == "dynamic-update-slice":
                update = operands[1]
            elif op == "fusion" and roots[called][4] == "dynamic-update-slice":
                update = roots[called][5][1]
            if update is not None and prod(shapes[update]) < layer_elems:
                continue    # a row written in place
            moves.append(f"{name} = {op} {dtype}{list(dims)}{layout}")
    assert not moves, moves
    assert len(output_copies) <= len(leaves), output_copies
    layer_bytes = sum(prod(l.shape[1:]) * l.dtype.itemsize for l in leaves)
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes
