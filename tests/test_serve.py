"""Serving engine: generation, continuous batching, AI-tax reporting."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.model import build_model
from repro.serve.engine import Request, ServingEngine

KEY = jax.random.PRNGKey(0)


def _engine(arch="llama3-8b", slots=2, cache_len=48):
    cfg = get_config(arch, smoke=True).replace(dtype="float32")
    model = build_model(cfg)
    params = model.init(KEY)
    return ServingEngine(model, params, batch_slots=slots,
                         cache_len=cache_len), cfg


def test_engine_generates_to_completion():
    eng, cfg = _engine()
    rng = np.random.default_rng(0)
    for rid in range(5):
        eng.submit(Request(rid, rng.integers(0, cfg.vocab_size, 12),
                           max_tokens=6))
    done = eng.run()
    assert len(done) == 5
    assert all(len(r.tokens) == 6 for r in done)
    assert all(0 <= t < cfg.vocab_size for r in done for t in r.tokens)


def test_engine_greedy_matches_manual_decode():
    eng, cfg = _engine(slots=1)
    model, params = eng.model, eng.params
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab_size, 10)
    eng.submit(Request(0, prompt, max_tokens=4))
    done = eng.run()
    # manual greedy
    logits, cache = model.prefill(params, {"tokens": jnp.asarray(prompt[None])},
                                  cache_len=eng.cache_len)
    toks = [int(jnp.argmax(logits[0]))]
    for _ in range(3):
        lg, cache = model.decode_step(params, cache,
                                      jnp.asarray([[toks[-1]]], jnp.int32))
        toks.append(int(jnp.argmax(lg[0])))
    assert done[0].tokens == toks


def test_engine_continuous_batching_refills_slots():
    eng, cfg = _engine(slots=2)
    rng = np.random.default_rng(2)
    for rid in range(6):
        eng.submit(Request(rid, rng.integers(0, cfg.vocab_size, 8),
                           max_tokens=3))
    done = eng.run()
    assert len(done) == 6                # 6 requests through 2 slots


def test_engine_tax_report_structure():
    eng, cfg = _engine()
    rng = np.random.default_rng(3)
    eng.submit(Request(0, rng.integers(0, cfg.vocab_size, 8), max_tokens=3))
    eng.run()
    rep = eng.tax_report()
    assert set(rep) >= {"ai_fraction", "tax_fraction", "per_stage"}
    assert "decode" in rep["per_stage"] and "prefill" in rep["per_stage"]
    assert 0.0 <= rep["ai_fraction"] <= 1.0


def test_engine_respects_cache_capacity():
    eng, cfg = _engine(slots=1, cache_len=16)
    rng = np.random.default_rng(4)
    eng.submit(Request(0, rng.integers(0, cfg.vocab_size, 10),
                       max_tokens=100))     # would overflow without eviction
    done = eng.run()
    assert done[0].done
    assert len(done[0].tokens) <= 16


def _prompts(cfg, n, length, seed=7):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, length) for _ in range(n)]


def _run_engine(scheduler, reqs, slots=2, cache_len=48):
    eng, cfg = _engine(slots=slots, cache_len=cache_len)
    eng = ServingEngine(eng.model, eng.params, batch_slots=slots,
                        cache_len=cache_len, scheduler=scheduler)
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    return eng, done


@pytest.mark.parametrize("scheduler", ["continuous", "slot"])
def test_engine_schedulers_agree(scheduler):
    """Both schedulers produce the greedy stream for every request."""
    eng, cfg = _engine(slots=2)
    p = _prompts(cfg, 5, 9)
    _, done1 = _run_engine("slot", [Request(i, p[i], max_tokens=5)
                                    for i in range(5)])
    _, done2 = _run_engine(scheduler, [Request(i, p[i], max_tokens=5)
                                       for i in range(5)])
    tok1 = {r.rid: r.tokens for r in done1}
    tok2 = {r.rid: r.tokens for r in done2}
    assert tok1 == tok2


def test_engine_mid_flight_admit_joins_without_stalling_residents():
    """A request admitted into a freed slot must not perturb the slots
    still decoding: A and B's token streams are identical with and
    without C in the system, and C's decode overlaps A's."""
    eng, cfg = _engine(slots=2)
    pa, pb, pc = _prompts(cfg, 3, 8, seed=11)

    def make():
        return [Request(0, pa, max_tokens=10),   # long-running resident
                Request(1, pb, max_tokens=3),    # frees its slot early
                Request(2, pc, max_tokens=4)]    # joins mid-flight of A

    _, done_ab = _run_engine("continuous", make()[:2])
    eng3, done_abc = _run_engine("continuous", make())
    ab = {r.rid: r.tokens for r in done_ab}
    abc = {r.rid: r.tokens for r in done_abc}
    assert abc[0] == ab[0] and abc[1] == ab[1]
    # C genuinely joined the running batch: its prefill lands before A's
    # last decode tick, and A keeps producing after C's admission
    c_prefill_end = max(e.t_end for e in eng3.log.events
                        if e.request_id == 2 and e.stage == "prefill")
    a_decodes_after = [e for e in eng3.log.events
                       if e.request_id == 0 and e.stage == "decode"
                       and e.t_start >= c_prefill_end]
    assert a_decodes_after, "admitting C stalled resident slot A"


def test_engine_same_seed_runs_bit_identical():
    """Two fresh engines over the same params and workload produce
    bit-identical token streams (lock-step batched decode is still
    deterministic)."""
    eng, cfg = _engine(slots=2)
    p = _prompts(cfg, 4, 10, seed=13)
    _, d1 = _run_engine("continuous", [Request(i, p[i], max_tokens=6)
                                       for i in range(4)])
    _, d2 = _run_engine("continuous", [Request(i, p[i], max_tokens=6)
                                       for i in range(4)])
    assert {r.rid: r.tokens for r in d1} == {r.rid: r.tokens for r in d2}


@pytest.mark.parametrize("scheduler", ["continuous", "slot"])
def test_engine_max_tokens_one_emits_exactly_one_token(scheduler):
    """max_tokens bounds generated tokens INCLUDING the prefill token:
    max_tokens=1 emits one token and never runs a decode step (the
    off-by-one used to emit two)."""
    eng, cfg = _engine(slots=2)
    p = _prompts(cfg, 3, 8, seed=17)
    eng, done = _run_engine(scheduler, [Request(i, p[i], max_tokens=1)
                                        for i in range(3)])
    assert len(done) == 3
    assert all(len(r.tokens) == 1 for r in done)
    assert not [e for e in eng.log.events if e.stage == "decode"]


@pytest.mark.parametrize("scheduler", ["continuous", "slot"])
def test_engine_transfer_ledger_accounts_every_d2h_byte(scheduler):
    """Every physically fetched device->host byte in the fast-path run
    is on the transfer ledger (the per-token ``cur_len`` sync of the
    old engine was invisible to the tax accounting)."""
    eng, cfg = _engine(slots=2)
    p = _prompts(cfg, 4, 8, seed=19)
    eng, done = _run_engine(scheduler, [Request(i, p[i], max_tokens=4)
                                        for i in range(4)])
    assert len(done) == 4
    assert eng.d2h_syncs > 0
    booked = eng.log.transfer_bytes()["d2h"]
    assert booked == eng.d2h_bytes, (
        f"ledger books {booked} d2h bytes, engine fetched {eng.d2h_bytes}")


def test_engine_decode_d2h_roundtrips_collapse_with_batching():
    """At full occupancy the continuous scheduler pays one d2h fetch per
    tick, not per token: decode-phase round-trips drop slots-fold."""
    eng, cfg = _engine()
    p = _prompts(cfg, 4, 8, seed=23)
    mk = lambda: [Request(i, p[i], max_tokens=5) for i in range(4)]
    slot_eng, _ = _run_engine("slot", mk(), slots=2)
    cont_eng, _ = _run_engine("continuous", mk(), slots=2)
    # 4 prefill fetches either way; decode fetches: 16 vs 8 ticks
    slot_decode = slot_eng.d2h_syncs - 4
    cont_decode = cont_eng.d2h_syncs - 4
    assert slot_decode == 2 * cont_decode


def test_engine_cache_len_768_traces():
    """cache_len=768 (not a multiple of the default KV tile) through the
    Pallas decode kernel in interpret mode — the blk_k legalization
    regression at engine level."""
    from repro.kernels import ops
    eng, cfg = _engine(slots=1, cache_len=768)
    rng = np.random.default_rng(29)
    with ops.default_impl("pallas_interpret"):
        eng.submit(Request(0, rng.integers(0, cfg.vocab_size, 8),
                           max_tokens=2))
        done = eng.run()
    assert len(done) == 1 and len(done[0].tokens) == 2


def test_engine_ttft_samples_cover_all_requests():
    eng, cfg = _engine(slots=2)
    p = _prompts(cfg, 4, 8, seed=31)
    eng, done = _run_engine("continuous", [Request(i, p[i], max_tokens=3)
                                           for i in range(4)])
    ttfts = eng.ttft_samples()
    assert len(ttfts) == 4 and all(t > 0 for t in ttfts)


def test_engine_traces_once_per_kernel_impl():
    """The models read ops' default impl while tracing: a step compiled
    under one implementation must not be reused under another."""
    from repro.kernels import ops
    from repro.serve import engine as E
    eng, cfg = _engine(slots=1, cache_len=40)
    prompt = np.random.default_rng(37).integers(0, cfg.vocab_size, 8)
    before = E._step_batched_fused._cache_size()
    for impl in ("xla", "pallas_interpret"):
        with ops.default_impl(impl):
            eng.submit(Request(len(impl), prompt, max_tokens=2))
            eng.run()
    assert E._step_batched_fused._cache_size() == before + 2


PHASES = ("pre_admit", "prefill", "transfer", "pre_insert", "decode",
          "post_tokens")


def test_engine_tick_phases_are_tagged_and_resolve():
    """Each continuous tick logs one span per host phase, all tagged with
    the tick's number, in order and without overlap; every stage name
    resolves through the canonical table."""
    from repro.core.events import categorize
    p = _prompts(_engine()[1], 5, 8, seed=41)
    eng, done = _run_engine("continuous", [Request(i, p[i], max_tokens=4)
                                           for i in range(5)])
    assert len(done) == 5
    by_tick = {}
    for rec in eng.log._records:
        if "tick" in rec.meta:
            by_tick.setdefault(rec.meta["tick"], []).append(rec)
    assert sorted(by_tick) == list(range(eng._tick_no))
    for n, recs in by_tick.items():
        assert all(r.stage in PHASES for r in recs)
        assert all(categorize(r.stage, default=None) for r in recs)
        assert all(a.t_end <= b.t_start for a, b in zip(recs, recs[1:]))
        stages = [r.stage for r in recs]
        assert stages[0] == "pre_admit"
        if "decode" in stages:
            assert stages[-4:] == ["transfer", "decode", "transfer",
                                   "post_tokens"]
            dec = recs[-3]
            assert dec.meta["slots_busy"] == len(dec.rids)
            assert dec.meta["queue_depth"] >= 0
            assert [r.meta["direction"] for r in (recs[-4], recs[-2])] == \
                ["h2d", "d2h"]
    # the read view carries the tag on every per-request slice
    assert {e.meta["tick"] for e in eng.log.events
            if "tick" in e.meta} == set(by_tick)


def test_engine_full_tick_stores_few_records():
    """A 32-slot tick that admits nothing stores one record per phase,
    not one event per slot and crossing; the read view still gives one
    event per slot and phase."""
    eng, cfg = _engine(slots=32, cache_len=32)
    for i, prompt in enumerate(_prompts(cfg, 32, 8, seed=43)):
        eng.submit(Request(i, prompt, max_tokens=8))
    eng.run(max_steps=1)                       # admits all 32, decodes
    assert all(r is not None for r in eng.active)
    n_rec, n_ev = len(eng.log._records), len(eng.log.events)
    eng.run(max_steps=1)
    assert len(eng.log._records) - n_rec == 5
    new = eng.log.events[n_ev:]
    assert len(new) == 5 * 32
    assert {e.request_id for e in new} == set(range(32))
    assert eng.log.transfer_bytes()["d2h"] == eng.d2h_bytes


def test_engine_tick_spans_nest_in_the_profiler_trace(tmp_path):
    """Under the profiler, each tick is a step ``engine.tick`` on the
    host line of the thread that ran it, with the phase spans inside;
    a collection shows as ``python.gc``."""
    import gc
    from jax.profiler import ProfileData
    eng, cfg = _engine(slots=2)
    p = _prompts(cfg, 3, 8, seed=47)
    eng.submit(Request(0, p[0], max_tokens=3))
    eng.run()                                  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in (1, 2):
            eng.submit(Request(i, p[i], max_tokens=3))
        eng.run()
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    pb = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    lines = [[(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for e in line.events]
             for plane in ProfileData.from_file(str(pb)).planes
             for line in plane.lines]
    line = next(ev for ev in lines if any(n == "engine.tick" for n, *_ in ev))
    ticks = [(s, e) for n, s, e in line if n == "engine.tick"]
    assert len(ticks) == 2
    for want in ("engine.h2d.decode", "engine.decode", "engine.post_tokens",
                 "engine.pre_admit", "engine.prefill", "engine.d2h.prefill"):
        spans = [(s, e) for n, s, e in line if n == want]
        assert spans, want
        assert all(any(a <= s and e <= b for a, b in ticks)
                   for s, e in spans), want
    assert any(n == "python.gc" for n, *_ in line)
