"""Bring-up smoke run: the system's main path on one TPU chip.

    python chip_smoke.py          # from the repository root, on a TPU host

One process, two phases, each run under compiled Pallas kernels
(``ops.default_impl("pallas")``) and again under ``"xla"`` on the same
chip, which is the reference it is checked against:

  A. Face recognition, the paper's application: ``StreamingPipeline``
     with the device-resident identify program and device-side
     decode/letterbox/NMS, for a few dozen frames. Recall must clear
     the test suite's bar and every face's (name, score) must match.
  B. LM serving at published width: granite-moe-3b-a800m in bf16 with
     random weights from a seed, built as ``repro.launch.serve`` builds
     it, 8 requests of 128 prompt tokens on 4 continuous-batching slots
     with a 512-token cache. Every request is served in full, and the
     prefill and one decode step's logits must lie as close to a
     float32-matmul run of the XLA program as the bf16 XLA path does.

Both phases assert that the compiled programs contain a
``tpu_custom_call``: the kernels were compiled by Mosaic, none was
interpreted and none gave way to a reference. The lines before the last
are smoke output (compile seconds, wall time, peak device memory,
agreement figures), not benchmark metrics. The last line is one JSON
object naming the device. Without a TPU, without the repository's
``src`` beside this file, or on any failed check, the script exits
non-zero and prints no result.
"""
from __future__ import annotations

import collections
import json
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

SEED = 0
KERNELS, REFERENCE = "pallas", "xla"

# Phase A. Recall bar: tests/test_pipeline.py. The frames decode and
# letterbox to the same pixels on both paths, and both identify
# contractions run at HIGHEST precision, which on the TPU leaves each
# hidden activation within ~4e-5 of float64 (not bit-exact float32).
# Which bucket and tiling a face batch gets depends on how the
# micro-batcher split the stream, so the two errors need not cancel;
# carried through the 256 -> 128 embedding and the normalization they
# bound a cosine score's difference by ~2e-3.
RECALL_BAR = 0.7
SCORE_TOL = 2e-3
N_FRAMES, FACE_BATCH = 48, 8

# Phase B. Both paths run the same bf16 network and differ only in the
# attention kernels. Where an attention dot takes float32 operands, the
# MXU's default precision rounds them to bf16, and through 32 layers and
# a top-8-of-40 router those roundings grow to several percent of the
# logits (relative L2 |a - b| / |b|). No fixed bound separates that from
# a kernel fault at this depth, so each run measures its own noise: the
# same XLA program with float32 matmuls
# (``jax.default_matmul_precision("highest")``) is the reference, and
# the kernels' logits may sit no further from it than twice the default
# XLA path's distance (or 1e-2, where that is smaller). A kernel that
# reads the wrong cache rows, heads or blocks lands at order one.
ARCH = "granite-moe-3b-a800m"
NOISE_FACTOR, NOISE_FLOOR = 2.0, 1e-2
N_REQUESTS, SLOTS, PROMPT_LEN, CACHE_LEN, MAX_TOKENS = 8, 4, 128, 512, 32


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


class CompileClock:
    """Seconds JAX spends in backend compilation (persistent-cache reads
    included, so a warm cache shows up as fewer seconds)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def assert_kernel(label: str, jitted, *args) -> None:
    """The compiled program of ``jitted(*args)`` holds a Mosaic kernel."""
    text = jitted.lower(*args).compile().as_text()
    check("tpu_custom_call" in text,
          f"{label}: no tpu_custom_call in the compiled HLO")
    say(f"{label}: compiled HLO holds tpu_custom_call")


# --------------------------------------------------------------------------
# Phase A: face recognition
# --------------------------------------------------------------------------

def _faces_by_frame(identities) -> dict[int, list[tuple[str, float]]]:
    by = collections.defaultdict(list)
    for rid, name, score in identities:
        by[rid].append((name, float(score)))
    return {rid: sorted(v) for rid, v in by.items()}


def phase_a() -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import facerec
    from repro.core.pipeline import StreamingPipeline
    from repro.kernels import ops
    from repro.preprocess import device

    runs = {}
    for impl in (KERNELS, REFERENCE):
        with ops.default_impl(impl):
            t0 = time.perf_counter()
            pipe = StreamingPipeline(n_frames=N_FRAMES, fast_path=True,
                                     placement="device",
                                     batch_size=FACE_BATCH, seed=SEED)
            res = pipe.run()
            wall = time.perf_counter() - t0
        say(f"A[{impl}] {N_FRAMES} frames, {len(res.identities)} faces "
            f"identified, recall {res.recall} ({res.matched}/"
            f"{res.ground_truth}), wall {wall:.3f} s")
        check(res.recall >= RECALL_BAR,
              f"A[{impl}] recall {res.recall} < {RECALL_BAR}")
        runs[impl] = (pipe, _faces_by_frame(res.identities))

    got, ref = runs[KERNELS][1], runs[REFERENCE][1]
    check(got.keys() == ref.keys(), "A: faces found in different frames")
    worst, n = 0.0, 0
    for rid, faces in ref.items():
        check(len(got[rid]) == len(faces), f"A: frame {rid} face count")
        for (gn, gs), (rn, rs) in zip(got[rid], faces):
            check(gn == rn, f"A: frame {rid} identity {gn} != {rn}")
            worst = max(worst, abs(gs - rs))
            n += 1
    say(f"A: {n} faces, identities equal, max |score diff| {worst:.3e} "
        f"(tolerance {SCORE_TOL})")
    check(worst <= SCORE_TOL, f"A: score diff {worst} > {SCORE_TOL}")

    # the kernels the pipeline ran, at the shapes it ran them
    pipe = runs[KERNELS][0]
    fused = pipe.fused_identifier
    h, w = pipe.video.h, pipe.video.w
    crops = jax.ShapeDtypeStruct(
        (FACE_BATCH, facerec.CROP_SIZE, facerec.CROP_SIZE, 3), jnp.uint8)
    assert_kernel("A fused identify", facerec._fused_identify_jit, crops,
                  fused.w1f, fused.b1, fused.w2, fused.gal_t, KERNELS)
    n = pipe.preprocess.frame_norm
    assert_kernel(
        "A yuv->rgb",
        jax.jit(lambda y: device.yuv_to_rgb(y, impl=KERNELS)),
        jax.ShapeDtypeStruct((1, 3, h, w), jnp.uint8))
    assert_kernel(
        "A letterbox",
        jax.jit(lambda f: device.letterbox_normalize(
            f, h // 2, w // 2, scale=n.scale, offset=n.offset,
            impl=KERNELS)),
        jax.ShapeDtypeStruct((1, h, w, 3), jnp.uint8))


# --------------------------------------------------------------------------
# Phase B: LM serving at published width
# --------------------------------------------------------------------------

def _rel_err(got, ref) -> float:
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


def _near_reference(what: str, dist: dict[str, float]) -> None:
    """The kernels' distance to the float32-matmul reference is within
    the noise the default XLA path shows against it."""
    bound = max(NOISE_FACTOR * dist[REFERENCE], NOISE_FLOOR)
    say(f"B: {what} logits, relative L2 error against the float32-matmul "
        f"reference: {KERNELS} {dist[KERNELS]:.3e}, {REFERENCE} "
        f"{dist[REFERENCE]:.3e} (bound {bound:.3e})")
    check(dist[KERNELS] <= bound,
          f"B: {what} logits under {KERNELS} are {dist[KERNELS]:.3e} from "
          f"the reference, over {bound:.3e}")


def phase_b() -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.launch.serve import load
    from repro.serve import engine as E

    t0 = time.perf_counter()
    model, params = load(ARCH, seed=SEED)
    jax.block_until_ready(params)
    n_params = sum(a.size for a in jax.tree.leaves(params))
    say(f"B: {ARCH} {n_params} params in {model.cfg.dtype}, built in "
        f"{time.perf_counter() - t0:.3f} s")
    prompts = np.random.default_rng(SEED).integers(
        0, model.cfg.vocab_size, (N_REQUESTS, PROMPT_LEN), dtype=np.int32)

    first = {}
    for impl in (KERNELS, REFERENCE):
        with ops.default_impl(impl):
            eng = E.ServingEngine(model, params, batch_slots=SLOTS,
                                  cache_len=CACHE_LEN,
                                  scheduler="continuous")
            for rid, p in enumerate(prompts):
                eng.submit(E.Request(rid, p, max_tokens=MAX_TOKENS))
            t0 = time.perf_counter()
            done = eng.run()
            wall = time.perf_counter() - t0
        n_tok = sum(len(r.tokens) for r in done)
        say(f"B[{impl}] served {len(done)} requests, {n_tok} tokens, "
            f"wall {wall:.3f} s")
        check(len(done) == N_REQUESTS
              and all(len(r.tokens) == MAX_TOKENS for r in done),
              f"B[{impl}] did not serve every request in full")
        first[impl] = {r.rid: r.tokens[0] for r in done}

    # prefill logits per request under the kernels, XLA, and XLA at
    # float32 matmul precision; each engine's first token must be its
    # own prefill's argmax
    def prefill(impl, p, precision=None):
        with jax.default_matmul_precision(precision):
            lg, cache = E._prefill(model, impl, params, jnp.asarray(p[None]),
                                   CACHE_LEN)
        return np.asarray(lg[0], np.float32), cache

    exact, dist = 0, {KERNELS: 0.0, REFERENCE: 0.0}
    blocks = model.init_cache(SLOTS, CACHE_LEN)["blocks"]
    for rid, p in enumerate(prompts):
        precise, _ = prefill(REFERENCE, p, "highest")
        for impl in (KERNELS, REFERENCE):
            logits, cache = prefill(impl, p)
            check(first[impl][rid] == int(np.argmax(logits)),
                  f"B[{impl}] request {rid}: first token is not the "
                  "prefill's argmax")
            dist[impl] = max(dist[impl], _rel_err(logits, precise))
        exact += first[KERNELS][rid] == first[REFERENCE][rid]
        if rid < SLOTS:   # the reference's cache feeds the decode check
            blocks = E._insert_slot(model, blocks, cache["blocks"],
                                    jnp.asarray(rid, jnp.int32))
    say(f"B: first tokens {exact}/{N_REQUESTS} equal to {REFERENCE}'s")
    _near_reference("prefill", dist)

    # one decode step over the same cache under all three
    tokens = np.array([first[REFERENCE][i] for i in range(SLOTS)], np.int32)
    packed = jnp.asarray(np.stack([tokens,
                                   np.full(SLOTS, PROMPT_LEN, np.int32)]))

    def step(impl, precision=None, packed=packed):
        with jax.default_matmul_precision(precision):
            return E._step_batched_plain(model, impl, params, blocks,
                                         packed)[0]

    precise = step(REFERENCE, "highest")
    _near_reference("decode step", {impl: _rel_err(step(impl), precise)
                                    for impl in (KERNELS, REFERENCE)})
    # for scale: one cache row too few, a masking bug
    short = packed.at[1].add(-1)
    say("B: an off-by-one kv_len moves decode logits by "
        f"{_rel_err(step(REFERENCE, 'highest', short), precise):.3e}")

    toks = jax.ShapeDtypeStruct((1, PROMPT_LEN), jnp.int32)
    assert_kernel("B prefill", E._prefill, model, KERNELS, params, toks,
                  CACHE_LEN)
    assert_kernel("B decode step", E._step_batched_fused, model, KERNELS,
                  params, blocks, packed)


def main() -> int:
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    say(f"compile cache at {enable_compile_cache()}")
    clock = CompileClock()
    for name, phase in (("A", phase_a), ("B", phase_b)):
        t0, c0, h0 = time.perf_counter(), clock.seconds, clock.cache_hits
        phase()
        say(f"phase {name}: wall {time.perf_counter() - t0:.3f} s, compile "
            f"{clock.seconds - c0:.3f} s, {clock.cache_hits - h0} "
            "persistent-cache hits")
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    say(f"peak device memory {peak if peak is not None else 'not reported'}"
        " bytes")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
