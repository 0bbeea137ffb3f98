"""Random weights of a served language model, drawn from the seed.

The benchmark makes the weights itself, for the program and, again and
independently, for the reference. Each matrix is a normal draw keyed by
the CRC-32 of its path in the program's parameter tree, scaled by
fan-in**-0.5 and rounded to the serving dtype; norm weights are one.
The embedding's rows are drawn at hidden_size**-0.5 like every other
matrix: at the unit scale the program's own initialisation gives them,
a tied head scores each token's own embedding far above every other
token, the model repeats its input, and a greedy check could not see a
fault in attention or in the cache.
"""
from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp

BLOCK = "['blocks']['l0']"


def leaves(c: dict) -> dict[str, tuple[tuple[int, ...], float]]:
    """Path -> (shape, scale) of every drawn matrix. Layers are stacked
    on a leading axis, as the program keeps them."""
    d, h, kv, hd = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    L, v, f = c["num_hidden_layers"], c["vocab_size"], c["intermediate_size"]
    out = {
        "['embed']['tok']": ((v, d), d ** -0.5),
        f"{BLOCK}['mix']['wq']": ((L, d, h * hd), d ** -0.5),
        f"{BLOCK}['mix']['wk']": ((L, d, kv * hd), d ** -0.5),
        f"{BLOCK}['mix']['wv']": ((L, d, kv * hd), d ** -0.5),
        f"{BLOCK}['mix']['wo']": ((L, h * hd, d), (h * hd) ** -0.5),
    }
    e = c.get("num_local_experts")
    if e:
        out[f"{BLOCK}['mlp']['router']"] = ((L, d, e), d ** -0.5)
        out[f"{BLOCK}['mlp']['wg']"] = ((L, e, d, f), d ** -0.5)
        out[f"{BLOCK}['mlp']['wi']"] = ((L, e, d, f), d ** -0.5)
        out[f"{BLOCK}['mlp']['wo']"] = ((L, e, f, d), f ** -0.5)
    else:
        out[f"{BLOCK}['mlp']['wg']"] = ((L, d, f), d ** -0.5)
        out[f"{BLOCK}['mlp']['wi']"] = ((L, d, f), d ** -0.5)
        out[f"{BLOCK}['mlp']['wo']"] = ((L, f, d), f ** -0.5)
    if not c["tie_word_embeddings"]:
        out["['embed']['head']"] = ((d, v), d ** -0.5)
    return out


def _draw(key, path: str, shape, scale, dtype):
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) % (2 ** 31))
    return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)


def for_program(c: dict, model, seed: int):
    """The program's parameter tree, made on the device in one jitted
    call. Matrices follow ``leaves``; every other leaf is a norm weight,
    which the program's metadata must declare as ones."""
    table = leaves(c)
    dtype = jnp.dtype(c["torch_dtype"])
    abstract = model.abstract_params(dtype)
    meta = jax.tree_util.tree_leaves(model.param_meta(),
                                     is_leaf=lambda x: hasattr(x, "init"))
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    plan = []
    for (path, leaf), m in zip(flat, meta):
        name = jax.tree_util.keystr(path)
        if name in table:
            if tuple(leaf.shape) != table[name][0]:
                raise ValueError(f"{name}: program shape {leaf.shape}, "
                                 f"configuration {table[name][0]}")
            plan.append((name, leaf.shape, table[name][1], leaf.dtype))
        elif m.init == "ones":
            plan.append((name, leaf.shape, None, leaf.dtype))
        else:
            raise ValueError(f"{name}: no rule for a {m.init!r} leaf")
    missing = set(table) - {p[0] for p in plan}
    if missing:
        raise ValueError(f"program has no leaves {sorted(missing)}")

    @jax.jit
    def make(key):
        return treedef.unflatten([
            jnp.ones(shape, dt) if scale is None else _draw(key, n, shape, scale, dt)
            for n, shape, scale, dt in plan])

    return make(jax.random.PRNGKey(seed % 2 ** 32))


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _one(key, path, shape, scale):
    return _draw(key, path, shape, scale, jnp.bfloat16)


def for_reference(c: dict, seed: int) -> dict[str, jax.Array]:
    """The same matrices, one call each, by short name (``mix.wq``,
    ``embed.tok``, ...)."""
    if c["torch_dtype"] != "bfloat16":
        raise ValueError("the reference draws bfloat16 weights only")
    key = jax.random.PRNGKey(seed % 2 ** 32)
    return {p.replace(BLOCK, "").strip("[]'").replace("']['", "."):
            _one(key, p, shape, scale) for p, (shape, scale) in leaves(c).items()}
