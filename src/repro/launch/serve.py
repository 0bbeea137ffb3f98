"""Serving driver CLI: batched requests through the ServingEngine with an
AI-tax report (the paper's measurement, applied to LM serving).

    PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --smoke \\
        --requests 8 --max-tokens 8
"""
from __future__ import annotations

import argparse
import functools

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import build_model
from repro.serve.engine import Request, ServingEngine


def load(arch: str, *, smoke: bool = False, seed: int = 0):
    """(model, params) as the serving CLI builds them.

    Published widths keep the config's dtype (bf16: llama3-8b at float32
    would be 32 GB against a 16 GB chip); smoke presets run in float32.
    Parameters are random from ``seed``, made in one jitted program so
    no float32 draw of a leaf is held beside the rest.
    """
    cfg = get_config(arch, smoke=smoke)
    if smoke:
        cfg = cfg.replace(dtype="float32")
    model = build_model(cfg)
    init = jax.jit(functools.partial(model.init, dtype=jnp.dtype(cfg.dtype)))
    return model, init(jax.random.PRNGKey(seed))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-tokens", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=96)
    args = ap.parse_args()

    enable_compile_cache()
    model, params = load(args.arch, smoke=args.smoke)
    cfg = model.cfg
    eng = ServingEngine(model, params, batch_slots=args.slots,
                        cache_len=args.cache_len)
    rng = np.random.default_rng(0)
    for rid in range(args.requests):
        eng.submit(Request(rid,
                           rng.integers(0, cfg.vocab_size, args.prompt_len),
                           max_tokens=args.max_tokens))
    done = eng.run()
    print(f"served {len(done)} requests "
          f"({sum(len(r.tokens) for r in done)} tokens)")
    rep = eng.tax_report()
    print(f"AI fraction {rep['ai_fraction']:.1%}  "
          f"tax {rep['tax_fraction']:.1%}")
    for stage, v in sorted(rep["per_stage"].items()):
        print(f"  {stage:<10} {v*1e3:8.2f} ms")


if __name__ == "__main__":
    main()
