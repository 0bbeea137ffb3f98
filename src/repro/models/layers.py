"""Parameter metadata + primitive layers (pure JAX, no flax).

Parameters are declared as trees of :class:`P` metadata (shape, logical
axes, initializer). A single metadata tree is the source of truth for
initialization, ``jax.eval_shape`` stand-ins, and sharding specs — so the
three can never drift apart.
"""
from __future__ import annotations

import zlib
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class P:
    """Parameter metadata. ``axes`` are logical-axis names per dimension."""
    shape: tuple[int, ...]
    axes: tuple[str | None, ...]
    init: str = "normal"          # normal | zeros | ones
    scale: float | None = None    # stddev; default fan_in**-0.5
    dtype: str | None = None      # override (norm scales stay f32)

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_meta_leaf(x) -> bool:
    return isinstance(x, P)


def stack_meta(tree, n: int):
    """Prepend a stacking dim (for scan-over-blocks parameters)."""
    return jax.tree.map(
        lambda p: P((n, *p.shape), (None, *p.axes), p.init, p.scale, p.dtype),
        tree, is_leaf=is_meta_leaf)


def init_params(tree, key: jax.Array, dtype=jnp.float32):
    """Materialize a metadata tree into arrays (deterministic per-path)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=is_meta_leaf)

    def make(path, p: P):
        dt = jnp.dtype(p.dtype) if p.dtype else dtype
        if p.init == "zeros":
            return jnp.zeros(p.shape, dt)
        if p.init == "ones":
            return jnp.ones(p.shape, dt)
        # crc32 of the leaf's path, not hash(): str hashes change with
        # every process, and weights from one seed must not
        name = jax.tree_util.keystr(path).encode()
        k = jax.random.fold_in(key, zlib.crc32(name) % (2**31))
        fan_in = p.shape[-2] if len(p.shape) >= 2 else p.shape[-1]
        scale = p.scale if p.scale is not None else fan_in ** -0.5
        return (jax.random.normal(k, p.shape, jnp.float32) * scale).astype(dt)

    return treedef.unflatten([make(path, p) for path, p in flat])


def abstract_params(tree, dtype=jnp.float32):
    """ShapeDtypeStruct stand-ins (dry-run: no allocation)."""
    return jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(
            p.shape, jnp.dtype(p.dtype) if p.dtype else dtype),
        tree, is_leaf=is_meta_leaf)


def meta_axes(tree):
    """Tree of logical-axes tuples, same structure as params."""
    return jax.tree.map(lambda p: p.axes, tree, is_leaf=is_meta_leaf)


def cast_params(params, dtype):
    """Compute-dtype cast: matrices -> dtype, 1-D scales stay put."""
    return jax.tree.map(
        lambda a: a.astype(dtype) if a.ndim > 1 and a.dtype == jnp.float32 else a,
        params)


# --------------------------------------------------------------------------
# primitive layers
# --------------------------------------------------------------------------

def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    n = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (n * w.astype(jnp.float32)).astype(x.dtype)


def layernorm(x: jax.Array, w: jax.Array, b: jax.Array,
              eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    n = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (n * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def groupnorm_heads(x: jax.Array, w: jax.Array, b: jax.Array,
                    eps: float = 64e-5) -> jax.Array:
    """Per-head groupnorm (RWKV output norm). x: (..., H, V)."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    n = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (n * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def norm_meta(cfg, d: int | None = None) -> dict:
    d = d or cfg.d_model
    if cfg.norm == "layernorm":
        return {"w": P((d,), (None,), "ones", dtype="float32"),
                "b": P((d,), (None,), "zeros", dtype="float32")}
    return {"w": P((d,), (None,), "ones", dtype="float32")}


def apply_norm(cfg, p: dict, x: jax.Array) -> jax.Array:
    if cfg.norm == "layernorm":
        return layernorm(x, p["w"], p["b"])
    return rmsnorm(x, p["w"])


def rope(x: jax.Array, positions: jax.Array, theta: float,
         rot_dims: int | None = None) -> jax.Array:
    """Rotary embedding, half-split convention.

    x: (B, S, H, D); positions: (S,) or (B, S). Rotates the first
    ``rot_dims`` dims of D (default: all).
    """
    B, S, H, D = x.shape
    R = rot_dims or D
    half = R // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions.astype(jnp.float32)[:, :, None] * freqs[None, None]  # (B,S,half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    xr = x[..., :R].astype(jnp.float32)
    x1, x2 = xr[..., :half], xr[..., half:]
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([rotated.astype(x.dtype), x[..., R:]], axis=-1)


def sincos_positions(S: int, d: int, offset=0) -> jax.Array:
    """Fixed sinusoidal position embeddings (whisper-style)."""
    pos = jnp.arange(S, dtype=jnp.float32) + offset
    half = d // 2
    freqs = jnp.exp(-jnp.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / (half - 1))
    ang = pos[:, None] * freqs[None]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[name]


# ---- dense MLP -----------------------------------------------------------

def mlp_meta(cfg, d_ff: int | None = None) -> dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    if cfg.mlp_kind == "plain":
        return {"wi": P((d, f), ("embed", "mlp")),
                "bi": P((f,), ("mlp",), "zeros"),
                "wo": P((f, d), ("mlp", "embed")),
                "bo": P((d,), (None,), "zeros")}
    return {"wg": P((d, f), ("embed", "mlp")),
            "wi": P((d, f), ("embed", "mlp")),
            "wo": P((f, d), ("mlp", "embed"))}


def mlp_apply(cfg, p: dict, x: jax.Array) -> jax.Array:
    act = act_fn(cfg.act)
    if cfg.mlp_kind == "plain":
        h = act(x @ p["wi"] + p["bi"].astype(x.dtype))
        return h @ p["wo"] + p["bo"].astype(x.dtype)
    return (act(x @ p["wg"]) * (x @ p["wi"])) @ p["wo"]


# ---- embeddings ----------------------------------------------------------

def embed_meta(cfg) -> dict:
    m = {"tok": P((cfg.vocab_size, cfg.d_model), ("vocab", "embed"), scale=1.0)}
    if not cfg.tie_embeddings:
        m["head"] = P((cfg.d_model, cfg.vocab_size), ("embed", "vocab"))
    return m


def embed_tokens(cfg, p: dict, tokens: jax.Array, dtype) -> jax.Array:
    x = p["tok"].astype(dtype)[tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, dtype)
    return x


def unembed(cfg, p: dict, x: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        return x @ p["tok"].astype(x.dtype).T
    return x @ p["head"].astype(x.dtype)
