"""The plain reference against the program, at smoke size on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference
import smoke
import weights
from drivers.lm_serving import program_config

CONFIGS = ["granite-moe-3b-a800m", "chameleon-34b-L6"]
# bfloat16 weights and activations through two layers: the program's
# logits lie within a few percent (relative L2) of float32's
BF16_REL = 0.05


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.fixture(scope="module", params=CONFIGS)
def setup(request):
    from repro.models.model import build_model
    c = smoke.config(request.param)
    model = build_model(program_config(c))
    return c, model, weights.for_program(c, model, 11), weights.for_reference(c, 11)


def test_reference_draws_the_programs_weights(setup):
    c, _, params, ref_w = setup
    flat = {jax.tree_util.keystr(k): v
            for k, v in jax.tree_util.tree_flatten_with_path(params)[0]}
    for path in weights.leaves(c):
        short = path.replace(weights.BLOCK, "").strip("[]'").replace("']['", ".")
        assert np.array_equal(np.asarray(flat[path]), np.asarray(ref_w[short])), path


def _served_rows(model, params, prompt, steps: int):
    """The program's logit rows: its prefill, then ``steps`` decode steps
    of slot 1 of a two-slot batched cache, feeding back its argmax."""
    from repro.serve import engine as E
    cache_len = 64
    logits, cache = E._prefill(model, "xla", params, jnp.asarray(prompt[None]),
                               cache_len)
    rows = [np.asarray(logits[0], np.float32)]
    blocks = model.init_cache(2, cache_len)["blocks"]
    blocks = E._insert_slot(model, blocks, cache["blocks"], jnp.int32(1))
    for j in range(steps):
        packed = jnp.asarray(np.array([[0, int(np.argmax(rows[-1]))],
                                       [0, len(prompt) + j]], np.int32))
        out, blocks = E._step_batched_plain(model, "xla", params, blocks, packed)
        rows.append(np.asarray(out[1], np.float32))
    return rows, np.asarray([int(np.argmax(r)) for r in rows], np.int32)


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_is_the_programs_arithmetic(name):
    """The program computed in float32 at HIGHEST precision, capacity
    drops and the batched cache included, equals the reference."""
    from repro.models.model import build_model
    c = smoke.config(name)
    model = build_model(program_config(dict(c, torch_dtype="float32")))
    bf16 = weights.for_program(c, build_model(program_config(c)), 5)
    params = jax.tree.map(lambda a: a.astype(jnp.float32), bf16)
    prompt = np.random.default_rng(2).integers(0, 256, 40).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        rows, served = _served_rows(model, params, prompt, 4)
    ref = reference.logits(c, weights.for_reference(c, 5), prompt, served, 64)
    for got, want in zip(rows, ref):
        assert _rel(got, want) < 1e-5


def test_served_bf16_program_lies_near_the_reference(setup):
    c, model, params, ref_w = setup
    prompt = np.random.default_rng(0).integers(0, c["vocab_size"], 40).astype(np.int32)
    rows, served = _served_rows(model, params, prompt, 3)
    ref = reference.logits(c, ref_w, prompt, served, 64)
    # decode steps route one token per slot and drop nothing; at prefill
    # a bfloat16 router can move a token past an expert's capacity that
    # float32 keeps (the float32 test above holds the drop logic)
    for got, want in zip(rows[1:], ref[1:]):
        assert _rel(got, want) < BF16_REL
    assert reference.worst_gap(ref[1:], served[1:]) < 0.1


def test_moe_capacity_drops_only_prompt_tokens():
    c = smoke.config("granite-moe-3b-a800m", moe_capacity_factor=0.25)
    assert reference.capacity(c, 40) == 8      # int(40*4/8*0.25) = 5 -> 8
    # with a tiny capacity the prompt's positions lose experts, and the
    # reference must still agree with the program, which drops the same
    from repro.models.model import build_model
    from repro.serve import engine as E
    model = build_model(program_config(c))
    params, w = weights.for_program(c, model, 3), weights.for_reference(c, 3)
    prompt = np.random.default_rng(1).integers(0, 256, 40).astype(np.int32)
    logits, _ = E._prefill(model, "xla", params, jnp.asarray(prompt[None]), 64)
    ref = reference.logits(c, w, prompt, np.zeros(1, np.int32), 64)
    assert _rel(logits[0], ref[0]) < BF16_REL
    full = reference.logits(dict(c, moe_capacity_factor=8.0), w, prompt,
                            np.zeros(1, np.int32), 64)
    assert _rel(full[0], ref[0]) > 2 * BF16_REL


@pytest.mark.parametrize("low", ["int8", "fp8"])
def test_control_departs_from_float32(setup, low):
    c, _, _, ref_w = setup
    prompt = np.arange(30, dtype=np.int32) % c["vocab_size"]
    served = np.arange(6, dtype=np.int32)
    f32 = reference.logits(c, ref_w, prompt, served, 64)
    ctl = reference.logits(c, ref_w, prompt, served, 64, low=low)
    assert 1e-3 < _rel(ctl, f32) < 0.5
