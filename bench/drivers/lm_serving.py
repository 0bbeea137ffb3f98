"""A language-model serving cell: ``repro``'s continuous-batching
``ServingEngine`` under an open loop.

One process, one engine. Set-up draws the model's weights from the seed
on the device in one call (``weights.py``), then warms every shape the
window will use by serving one request of each prompt length the mix
draws. Arrivals start the mix's ``preroll_s`` before the window, so the
window opens on slots as full as the rate keeps them. The loop submits
each request when it falls due and otherwise drives the engine one
scheduler tick at a time (``run(max_steps=1)``: admit into free slots
with a batch-1 prefill each, then one ragged decode step over the
occupied slots). Every token is timed when the tick that made it
returns to this loop, which is when a client could see it. After the
window the engine keeps ticking, with no new arrivals, until every
request submitted has its first token.

Then the device's peak memory is read, the program is freed, and a
sample of the finished requests, drawn from the seed with the one that
served the most tokens in it, is checked against ``reference.py``: the
number compared is the mean, over every served token of the sample, of
how far the token's logit lies below the reference's best at its
position (``mean_logit_gap``).
"""
from __future__ import annotations

import gc
import shutil
import time
from dataclasses import dataclass, field

import numpy as np

import reference
import traffic
import weights

TRACE_SECONDS = 8.0     # the traced part: the end of the window
DRAIN_SECONDS = 120.0   # how long to wait for late first tokens


@dataclass
class Client:
    """One request as the client sees it; times are seconds from the
    window's start, negative in the pre-roll."""
    t_due: float
    prompt: np.ndarray
    max_tokens: int
    t_first: float | None = None
    token_times: list = field(default_factory=list)
    tokens: list = field(default_factory=list)
    done: bool = False


@dataclass
class Tick:
    """One scheduler tick: when it ran, the prompt lengths it prefilled,
    each decoded slot's cache length after the step, and the requests
    left waiting for a slot."""
    t0: float
    t1: float
    prefill_lens: list
    decode_kv_lens: list
    queued: int = 0


@dataclass
class RunData:
    """Everything the metric readers (``bench/metrics``) see of a run.
    ``clients`` and ``ticks`` include the pre-roll's; ``events`` are the
    engine's EventLog spans (stage, start, end, meta) of the window."""
    config: dict
    mix: dict
    peaks: dict
    window_s: float
    setup_s: float
    clients: list
    ticks: list
    events: list
    trace: object = None     # trace.Summary of the traced span, or None
    traced_ticks: list = field(default_factory=list)

    def _in(self, t: float) -> bool:
        return 0.0 <= t <= self.window_s

    def ttft_s(self) -> list[float]:
        """Due time to first token, every request due in the window;
        a request that never got one counts as infinitely late."""
        return [(c.t_first - c.t_due) if c.t_first is not None else float("inf")
                for c in self.clients if self._in(c.t_due)]

    def itl_s(self) -> list[float]:
        """Gaps between consecutive tokens of a request, where the later
        token came inside the window."""
        out = []
        for c in self.clients:
            ts = c.token_times
            out += [b - a for a, b in zip(ts, ts[1:]) if self._in(b)]
        return out

    def tokens_in_window(self) -> int:
        """Prompt tokens of requests whose first token came in the window,
        and every token generated in it."""
        n = 0
        for c in self.clients:
            if c.t_first is not None and self._in(c.t_first):
                n += len(c.prompt)
            n += sum(1 for t in c.token_times if self._in(t))
        return n


@dataclass
class Outcome:
    data: RunData
    correct: bool
    attempted: int
    failed: int
    checks: list             # [(name, value, limit)]
    sample: list             # the clients checked against the reference
    memory_peak_bytes: int
    log: list                # lines for standard error


# what the program does where the published model scales (muP): a
# configuration's ``run_as`` may hold these keys at these values only
PROGRAM_SCALES = {"embedding_multiplier": lambda c: 1.0,
                  "attention_multiplier": lambda c: c["head_dim"] ** -0.5,
                  "residual_multiplier": lambda c: 1.0,
                  "logits_scaling": lambda c: 1.0}


def as_run(c: dict) -> dict:
    """The configuration as it is run: the file's published values with
    its ``run_as`` departures set over them."""
    return {**c, **c.get("run_as", {})}


def program_config(c: dict):
    """The program's ModelConfig for a configuration as run: the
    program's own entry for ``arch``, with the file's sizes set on it."""
    from repro.configs import get_config
    from repro.configs.base import MoEConfig
    cfg = get_config(c["arch"])
    kw = dict(n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
              n_heads=c["num_attention_heads"],
              n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
              vocab_size=c["vocab_size"], d_ff=c["intermediate_size"],
              tie_embeddings=c["tie_word_embeddings"],
              rope_theta=float(c["rope_theta"]),
              qk_norm=bool(c.get("qk_layernorm", False)),
              dtype=c["torch_dtype"])
    if c.get("num_local_experts"):
        kw["moe"] = MoEConfig(n_experts=c["num_local_experts"],
                              top_k=c["num_experts_per_tok"],
                              d_expert=c["intermediate_size"],
                              capacity_factor=c["moe_capacity_factor"])
    cfg = cfg.replace(**kw)
    # what the file cannot set on the program has to agree with it
    fixed = {"act": c["hidden_act"], "norm": "rmsnorm", "pos": "rope",
             "mlp_kind": "glu", "qkv_bias": c["attention_bias"],
             "embed_scale": False, "encdec": False}
    for k, v in fixed.items():
        if getattr(cfg, k) != v:
            raise ValueError(f"{c['arch']}: program has {k}={getattr(cfg, k)!r}, "
                             f"the configuration file {v!r}")
    if c["rms_norm_eps"] != 1e-6 or c.get("swin_norm"):
        raise ValueError(f"{c['arch']}: the program runs pre-norm RMSNorm at "
                         "eps 1e-6")
    for k, f in PROGRAM_SCALES.items():
        if k in c and c[k] != f(c):
            raise ValueError(f"{c['arch']}: the program runs {k} as {f(c)!r}, "
                             f"the configuration as {c[k]!r}")
    if len(cfg.block_pattern) != 1 or cfg.block_pattern[0].kind != "attn" \
            or cfg.block_pattern[0].window is not None \
            or cfg.block_pattern[0].moe != bool(c.get("num_local_experts")):
        raise ValueError(f"{c['arch']}: block pattern is not one global "
                         "attention layer with the file's MLP")
    return cfg


def _warm(E, eng, lengths, vocab: int, rng) -> None:
    """Serve one request of each prompt length to completion: compiles
    each prefill, the slot insert, the decode step and the engine's
    small programs."""
    for i, s in enumerate(lengths):
        eng.submit(E.Request(-1 - i, rng.integers(0, vocab, s, dtype=np.int32),
                             max_tokens=3))
    while any(eng.active) or eng.queue_depth:
        eng.run(max_steps=1)


def _tick(eng, inflight: dict, t_zero: float, annotate) -> Tick:
    before = {rid: len(req.tokens) for rid, (req, _) in inflight.items()}
    t0 = time.perf_counter() - t_zero
    with annotate("bench.tick"):
        done = eng.run(max_steps=1)
    t1 = time.perf_counter() - t_zero
    tick = Tick(t0, t1, [], [], eng.queue_depth)
    for rid, (req, cl) in inflight.items():
        old, new = before[rid], len(req.tokens)
        if new == old:
            continue
        cl.token_times += [t1] * (new - old)
        if old == 0:
            cl.t_first = t1
            tick.prefill_lens.append(len(cl.prompt))
        if new - old - (old == 0):
            tick.decode_kv_lens.append(len(cl.prompt) + new - 1)
    for req in done:
        _, cl = inflight.pop(req.rid)
        cl.done = True
        cl.tokens = list(req.tokens)
    return tick


def reference_rows(mix: dict) -> int:
    """The longest prompt and answer the mix can draw, padded to 512:
    the one sequence length the reference compiles for."""
    longest = max(_bounds(mix["prompt_len"])) + max(_bounds(mix["output_len"]))
    return -(-longest // 512) * 512


def _bounds(spec: dict) -> list[int]:
    return spec["choices"] if "choices" in spec else [spec["min"], spec["max"]]


def _sample(clients, rng, chk: dict):
    """Finished requests to check: the one that served the most tokens,
    then others drawn from the seed until ``served_tokens`` tokens and
    ``min_requests`` requests are in, or ``max_requests``."""
    done = [c for c in clients if c.done]
    if not done:
        return []
    first = max(range(len(done)), key=lambda i: len(done[i].tokens))
    picks, n = [done[first]], len(done[first].tokens)
    for i in rng.permutation(len(done)):
        if len(picks) >= chk["max_requests"] or (
                n >= chk["served_tokens"] and len(picks) >= chk["min_requests"]):
            break
        if i != first:
            picks.append(done[i])
            n += len(done[i].tokens)
    return picks


def reference_gaps(config: dict, seed: int, mix: dict, sample,
                   low: str | None = None) -> np.ndarray:
    """How far each served token of the sample lies below the float32
    reference's best at its position. With ``low``, the control in the
    program's place: at each position of the same prompts and served
    tokens, the token that the reference in ``low`` precision puts first."""
    w = weights.for_reference(config, seed)
    rows, out = reference_rows(mix), []
    for c in sample:
        served = np.asarray(c.tokens, np.int32)
        ref = reference.logits(config, w, c.prompt, served, rows)
        chosen = served if low is None else reference.logits(
            config, w, c.prompt, served, rows, low=low).argmax(axis=-1)
        out.append(reference.gaps(ref, chosen))
    return np.concatenate(out) if out else np.zeros(1)


def verdict(clients, sample, gaps: np.ndarray, limits: dict,
            chk: dict) -> tuple[list, bool]:
    """The numbers compared, each beside its limit, and whether every
    one is within it."""
    unserved = sum(c.t_first is None for c in clients)
    short = sum(c.done and len(c.tokens) != c.max_tokens for c in clients)
    checks = [("mean_logit_gap", float(gaps.mean()), limits["mean_logit_gap"]),
              ("requests_without_first_token", unserved, 0),
              ("requests_served_short", short, 0),
              ("sample_requests_missing",
               max(0, chk["min_requests"] - len(sample)), 0)]
    return checks, all(v <= lim for _, v, lim in checks)


def run(cell: dict, config: dict, seed: int, seconds: float, trace: bool, *,
        t_start: float, limits: dict, peaks: dict, trace_dir, compiles,
        mix: dict | None = None, keep_trace: bool = False) -> Outcome:
    import jax
    from repro.models.model import build_model
    from repro.serve import engine as E

    mix = mix or traffic.load_mix(cell["traffic"])
    config = as_run(config)
    cfg = program_config(config)
    model = build_model(cfg)
    params = weights.for_program(config, model, seed)
    arrivals = traffic.schedule(mix, seed, seconds, cfg.vocab_size)
    clients = [Client(a.t_due, a.prompt, a.max_tokens) for a in arrivals]
    eng = E.ServingEngine(model, params, batch_slots=mix["slots"],
                          cache_len=mix["cache_len"])
    rng = np.random.default_rng(seed % 2 ** 64)
    _warm(E, eng, sorted({len(a.prompt) for a in arrivals}), cfg.vocab_size, rng)
    n_events0 = len(eng.log.events)
    log = [f"set-up compiled {compiles.lowered} programs"]

    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        annotate = jax.profiler.TraceAnnotation
    else:
        from contextlib import nullcontext
        annotate = lambda name: nullcontext()
    trace_from = max(0.0, seconds - TRACE_SECONDS) if trace else float("inf")
    traced, traced_at, tracer = False, None, None

    gc.collect()
    gc.freeze()
    lowered0 = compiles.lowered
    preroll = mix.get("preroll_s", 0.0)
    t_zero = time.perf_counter() + preroll      # the window opens here
    setup_s = t_zero - preroll - t_start
    inflight, ticks, i, n = {}, [], 0, len(arrivals)
    while True:
        now = time.perf_counter() - t_zero
        if not traced and now >= trace_from and now < seconds:
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            tracer = annotate("bench.traced")
            tracer.__enter__()
            traced, traced_at = True, time.perf_counter() - t_zero
        if now >= seconds:
            break
        with annotate("bench.submit"):
            while i < n and arrivals[i].t_due <= now:
                a = arrivals[i]
                req = E.Request(a.rid, a.prompt, max_tokens=a.max_tokens)
                eng.submit(req)
                inflight[a.rid] = (req, clients[i])
                i += 1
        if inflight:
            ticks.append(_tick(eng, inflight, t_zero, annotate))
        else:
            due = arrivals[i].t_due if i < n else seconds
            wake = min(due, seconds) if traced else min(due, seconds, trace_from)
            with annotate("bench.idle"):
                time.sleep(max(0.0, wake - now))
    window_end = time.perf_counter() - t_zero
    if tracer is not None:
        tracer.__exit__(None, None, None)
        jax.profiler.stop_trace()
    lowered_in_window = compiles.lowered - lowered0
    window_events = [(ev.stage, ev.t_start - t_zero, ev.t_end - t_zero, ev.meta)
                     for ev in eng.log.events[n_events0:]
                     if ev.t_end >= t_zero]
    # late first tokens: keep ticking, no new arrivals
    deadline = time.perf_counter() + DRAIN_SECONDS
    while any(c.t_first is None for c in clients[:i]) and time.perf_counter() < deadline:
        _tick(eng, inflight, t_zero, annotate)
    gc.unfreeze()

    stats = jax.devices()[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    summary = None
    if trace:
        import trace as trace_mod
        pbs = sorted(trace_dir.rglob("*.xplane.pb"))
        summary = trace_mod.summarize(trace_mod.load(pbs[-1]))
        if not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
    del eng, params, inflight
    gc.collect()

    # correctness: the reference over a sample of what the run served
    chk = mix["check"]
    served = clients[:i]
    sample = _sample(served, rng, chk)
    t_ref = time.perf_counter()
    gaps = reference_gaps(config, seed, mix, sample)
    checks, correct = verdict(served, sample, gaps, limits, chk)
    in_window = sum(c.t_due >= 0 for c in served)
    log += [f"window {window_end:.3f} s after a {preroll:.1f} s pre-roll, "
            f"{i} requests submitted, {in_window} due in the window, "
            f"{len(ticks)} ticks, {lowered_in_window} programs compiled "
            "in the pre-roll and the window",
            f"reference checked {len(sample)} requests, "
            f"{sum(len(c.tokens) for c in sample)} served tokens, "
            f"in {time.perf_counter() - t_ref:.3f} s: gaps max {gaps.max():.6f}, "
            f"mean {gaps.mean():.6f}, nonzero {np.mean(gaps > 0):.4f}"]
    traced_ticks = []
    if traced:
        traced_ticks = [t for t in ticks if t.t0 >= traced_at]
    data = RunData(config, mix, peaks, seconds, setup_s, served, ticks,
                   window_events, summary, traced_ticks)
    nums = {name: v for name, v, _ in checks}
    failed = nums["requests_without_first_token"] + nums["requests_served_short"]
    return Outcome(data, correct, i, failed, checks, sample, peak, log)
