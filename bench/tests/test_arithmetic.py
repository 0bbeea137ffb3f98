"""Traffic generation, percentiles, spreads and the window arithmetic."""
import math

import numpy as np
import pytest

import smoke
import stats
import traffic
from drivers.lm_serving import Client, RunData


def _mix(**kw):
    return smoke.mix(rate=8.0, **kw)


def test_every_seed_offers_the_same_work_in_another_order():
    a = traffic.schedule(_mix(), 1, 30.0, 1000)
    b = traffic.schedule(_mix(), 2 ** 31 + 9, 30.0, 1000)
    assert len(a) == len(b) > 150
    for key in (lambda x: len(x.prompt), lambda x: x.max_tokens):
        assert sorted(map(key, a)) == sorted(map(key, b))
        assert list(map(key, a)) != list(map(key, b))
    assert a[-1].t_due == pytest.approx(b[-1].t_due) and a[-1].t_due < 30.0
    assert [x.t_due for x in a] == sorted(x.t_due for x in a)
    assert not np.array_equal(a[0].prompt, b[0].prompt)


def test_same_seed_same_schedule():
    a = traffic.schedule(_mix(), 7, 10.0, 1000)
    b = traffic.schedule(_mix(), 7, 10.0, 1000)
    assert [(x.t_due, x.max_tokens) for x in a] == [(x.t_due, x.max_tokens) for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_length_quota_follows_the_weights():
    mix = _mix(prompt_len={"choices": [10, 20, 30], "weights": [0.5, 0.3, 0.2]})
    a = traffic.schedule(mix, 3, 50.0, 1000)
    counts = np.bincount([len(x.prompt) // 10 for x in a])[1:]
    assert np.abs(counts / len(a) - [0.5, 0.3, 0.2]).max() < 1.0 / len(a) + 1e-9


def test_lognormal_lengths_keep_their_median_and_bounds():
    lens = traffic._lengths(401, {"dist": "lognormal", "median": 128,
                                  "sigma": 0.8, "min": 32, "max": 512})
    assert int(np.median(lens)) == 128
    assert lens.min() >= 32 and lens.max() <= 512


def test_bursts_crowd_arrivals_into_their_windows():
    mix = _mix(arrivals={"process": "poisson", "rate_per_s": 8.0,
                         "bursts": {"every_s": 10.0, "len_s": 2.0, "factor": 4.0}})
    t = np.array([x.t_due for x in traffic.schedule(mix, 5, 60.0, 100)])
    in_burst = (t % 10.0) < 2.0
    # 2 s at 32/s against 8 s at 8/s per period: half the arrivals
    assert 0.4 < in_burst.mean() < 0.6


def test_shared_prefixes():
    mix = _mix(shared_prefix={"groups": 2, "len": 8, "share": 1.0})
    a = traffic.schedule(mix, 5, 10.0, 1000)
    heads = {tuple(x.prompt[:8]) for x in a}
    assert len(heads) == 2


def test_nearest_rank_percentile():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 0.95) == 95
    assert stats.percentile([3.0, float("inf"), 1.0], 0.95) == float("inf")
    assert stats.percentile([5.0], 0.5) == 5.0
    with pytest.raises(ValueError):
        stats.percentile([], 0.5)


def test_window_arithmetic():
    p = np.zeros(10, np.int32)
    clients = [
        # due in the pre-roll: one gap and one token close in the window
        Client(-1.0, p, 3, t_first=-0.8, token_times=[-0.8, -0.1, 0.2], done=True),
        Client(0.0, p, 3, t_first=0.5, token_times=[0.5, 0.5, 0.7], done=True),
        Client(1.0, p, 4, t_first=1.5, token_times=[1.5, 1.8, 2.4, 2.6]),
        Client(1.9, p, 2, t_first=None),
    ]
    d = RunData({}, {}, {}, 2.5, 1.0, clients, [], [])
    assert d.ttft_s()[:2] == [0.5, 0.5] and math.isinf(d.ttft_s()[2])
    assert len(d.ttft_s()) == 3
    assert sorted(d.itl_s()) == pytest.approx([0.0, 0.2, 0.3, 0.3, 0.6])
    # prompts of the two first tokens in the window, and seven tokens in it
    assert d.tokens_in_window() == 10 + 10 + 7
    import run as R
    assert R.reader("itl_p50_ms")(d) == pytest.approx(300.0)
    assert R.reader("itl_p93_ms")(d) == pytest.approx(600.0)
    assert R.reader("ttft_p50_ms")(d) == pytest.approx(500.0)
    assert R.reader("setup_s")(d) == 1.0


def test_the_preroll_leads_the_window_with_work_of_its_own():
    mix = _mix(preroll_s=5.0)
    a = traffic.schedule(mix, 1, 20.0, 1000)
    b = traffic.schedule(mix, 2 ** 31 + 9, 20.0, 1000)
    assert a[0].t_due >= -5.0 and a[0].t_due < 0.0
    assert [x.rid for x in a] == list(range(len(a)))
    assert [x.t_due for x in a] == sorted(x.t_due for x in a)
    for part in (lambda x: x.t_due < 0, lambda x: x.t_due >= 0):
        pa, pb = [x for x in a if part(x)], [x for x in b if part(x)]
        assert len(pa) == len(pb) > 20
        for key in (lambda x: len(x.prompt), lambda x: x.max_tokens):
            assert sorted(map(key, pa)) == sorted(map(key, pb))
    # the window is the same as a mix without a pre-roll would give
    c = traffic.schedule(_mix(preroll_s=0.0), 1, 20.0, 1000)
    assert sorted(x.t_due for x in a if x.t_due >= 0) == pytest.approx(
        sorted(x.t_due for x in c))


def test_operation_and_byte_counts_from_shapes():
    import json

    import flops
    from conftest import BENCH
    g = json.loads((BENCH / "configs" / "granite-moe-3b-a800m.json").read_text())
    d, layers = 1536, 32
    attn = d * 1536 + 2 * d * 512 + 1536 * d
    mlp_active = 8 * 3 * d * 512 + d * 40
    assert flops.token_linear_flops(g) == 2 * layers * (attn + mlp_active)
    # causal scores: S(S+1)/2 pairs of 24 heads of 64, two products each
    s = 1024
    assert flops.prefill_flops(g, s) == pytest.approx(
        s * flops.token_linear_flops(g) + layers * 4 * 24 * 64 * s * (s + 1) / 2
        + 2 * d * 49155)
    # one active slot routes to 8 experts; 32 slots touch nearly all 40
    assert flops.experts_touched(g, 1) == pytest.approx(8)
    assert flops.experts_touched(g, 32) > 39.9
    kv_row = layers * 2 * 8 * 64 * 2
    one = flops.decode_bytes(g, [100])
    weights = 2 * (layers * (attn + 8 * 3 * d * 512 + d * 40) + d * 49155)
    assert one == pytest.approx(weights + kv_row * 101)
