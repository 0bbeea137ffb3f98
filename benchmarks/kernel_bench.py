"""Kernel micro-benchmarks: XLA path wall-time on whatever backend runs
it + analytic TPU-v5e projections for the Pallas kernels (FLOPs /
ideal-bytes at the kernel's actual tiling).

Off a TPU the wall-times are CPU-indicative only; the derived column
carries the TPU-side roofline projection. The tuned Pallas matmul's
sanity row runs the compiled kernel, so it appears only on a TPU."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import row, timed
from repro.kernels import ops
from repro.roofline import hw


def _proj(flops, byts):
    t = max(flops / hw.PEAK_FLOPS_BF16, byts / hw.HBM_BW)
    bound = "compute" if flops / hw.PEAK_FLOPS_BF16 >= byts / hw.HBM_BW \
        else "memory"
    return t, bound


def run() -> list[str]:
    out = []
    key = jax.random.PRNGKey(0)

    # flash attention: B=4, S=2048, H=16, D=128 bf16
    B, S, H, KV, D = 4, 2048, 16, 4, 128
    q = jax.random.normal(key, (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(key, (B, S, KV, D), jnp.bfloat16)
    v = jax.random.normal(key, (B, S, KV, D), jnp.bfloat16)
    f = jax.jit(lambda q, k, v: ops.attention(q, k, v, causal=True,
                                              impl="xla"))
    f(q, k, v).block_until_ready()
    _, us = timed(lambda: f(q, k, v).block_until_ready(), repeat=3)
    flops = 4 * B * H * S * S * D / 2          # causal
    byts = (q.nbytes + k.nbytes + v.nbytes + q.nbytes)
    t, bound = _proj(flops, byts)
    out.append(row("kernel/flash_attention_2k", us,
                   f"tpu_roofline_us={t*1e6:.0f};bound={bound}"))

    # decode attention: B=64, L=8192 cache
    B, L = 64, 8192
    qd = jax.random.normal(key, (B, 1, H, D), jnp.bfloat16)
    kd = jax.random.normal(key, (B, L, KV, D), jnp.bfloat16)
    vd = jax.random.normal(key, (B, L, KV, D), jnp.bfloat16)
    kl = jnp.full((B,), L, jnp.int32)
    g = jax.jit(lambda q, k, v: ops.decode_attention(q, k, v, kv_len=kl))
    g(qd, kd, vd).block_until_ready()
    _, us = timed(lambda: g(qd, kd, vd).block_until_ready(), repeat=3)
    byts = kd.nbytes + vd.nbytes
    flops = 4 * B * H * L * D
    t, bound = _proj(flops, byts)
    out.append(row("kernel/decode_attention_8k", us,
                   f"tpu_roofline_us={t*1e6:.0f};bound={bound}"))

    # rwkv scan: B=8, S=1024, H=16, K=V=64
    B, S, Hh, K = 8, 1024, 16, 64
    r = jax.random.normal(key, (B, S, Hh, K))
    w = jax.nn.sigmoid(jax.random.normal(key, (B, S, Hh, K))) * 0.5 + 0.45
    kk = jax.random.normal(key, (B, S, Hh, K)) * 0.3
    vv = jax.random.normal(key, (B, S, Hh, K))
    u = jax.random.normal(key, (Hh, K)) * 0.1
    h = jax.jit(lambda r, w, k, v: ops.rwkv_scan(r, w, k, v, u, impl="xla")[0])
    h(r, w, kk, vv).block_until_ready()
    _, us = timed(lambda: h(r, w, kk, vv).block_until_ready(), repeat=2)
    flops = 6 * B * S * Hh * K * K             # state update + readout
    byts = 4 * r.nbytes + r.nbytes             # r,w,k,v in + o out (f32)
    t, bound = _proj(flops, byts)
    out.append(row("kernel/rwkv_scan_1k", us,
                   f"tpu_roofline_us={t*1e6:.0f};bound={bound}"))

    # resize: 1080p-equivalent plane
    img = jax.random.uniform(key, (1080, 1920, 3), jnp.float32)
    rz = jax.jit(lambda x: ops.resize_bilinear(x, 540, 960))
    rz(img).block_until_ready()
    _, us = timed(lambda: rz(img).block_until_ready(), repeat=3)
    byts = img.nbytes + img.nbytes // 4
    flops = 2 * 540 * 960 * 3 * (1080 + 1920)  # separable matmul form
    t, bound = _proj(flops, byts)
    out.append(row("kernel/resize_1080p", us,
                   f"tpu_roofline_us={t*1e6:.0f};bound={bound}"))

    # matmul: the embedder's layer-1 contraction, default vs autotuned
    # tiling — analytic TPU projection at each tiling, plus (on a TPU)
    # the compiled kernel as numerical sanity for the tuned blocks
    from repro.kernels import autotune, ref
    M, K, N = 512, 3072, 256
    default = {"blk_m": 128, "blk_n": 128, "blk_k": 512}
    tuned = autotune.matmul_tiling(M, K, N, "float32")
    for label, blocks in (("default", default), ("autotuned", tuned)):
        proj = autotune.matmul_cost_us(M, K, N, "float32", **blocks)
        out.append(row(
            f"kernel/matmul_embed_{label}", 0.0,
            f"tpu_proj_us={proj:.2f};"
            f"blocks=m{blocks['blk_m']}n{blocks['blk_n']}k{blocks['blk_k']}"))
    if jax.default_backend() != "tpu":
        return out
    a = jax.random.normal(key, (M, K), jnp.float32) * 0.1
    b = jax.random.normal(key, (K, N), jnp.float32) * 0.1
    mm = jax.jit(lambda a, b: ops.matmul(a, b, impl="pallas", **tuned))
    mm(a, b).block_until_ready()     # warm: compile
    got, us = timed(lambda: mm(a, b).block_until_ready(), repeat=2)
    err = float(jnp.max(jnp.abs(got - ref.matmul(a, b))))
    speedup = autotune.matmul_cost_us(M, K, N, "float32", **default) \
        / autotune.matmul_cost_us(M, K, N, "float32", **tuned)
    out.append(row("kernel/matmul_embed_tuned_sanity", us,
                   f"max_err={err:.1e};"
                   f"tuned_vs_default_proj={speedup:.2f}x"))
    return out


if __name__ == "__main__":
    print("\n".join(run()))
