"""The readers of the engine's tick spans, on a smoke run on the CPU."""
import time

import pytest

import run as R
import smoke
from stats import percentile


@pytest.fixture(scope="module")
def data():
    from drivers import lm_serving as D
    out = D.run({"name": "smoke", "traffic": None},
                smoke.config("chameleon-34b-L6"), 2 ** 31 + 5, 1.0, False,
                t_start=time.perf_counter(), limits={"mean_logit_gap": 0.005},
                peaks={}, trace_dir=None, compiles=R.CompileCounter(),
                mix=smoke.mix())
    assert out.correct and out.data.events
    return out.data


def test_tick_host_ms_is_a_part_of_the_tick(data):
    v = R.reader("tick_host_ms")(data)
    wall = percentile([t.t1 - t.t0 for t in data.ticks if t.t0 >= 0], 0.5)
    assert v is not None and 0 < v < wall * 1e3


def test_ai_tax_share_is_a_share(data):
    v = R.reader("ai_tax_share")(data)
    assert v is not None and 0 < v < 100


def test_the_traced_part_is_left_out(data):
    """Spans after the first traced tick are not read."""
    late = data.ticks[len(data.ticks) // 2]
    cut = [t for t in data.ticks if t.t0 >= late.t0]
    full = R.reader("ai_tax_share")(data)
    data.traced_ticks = cut
    try:
        assert R.reader("ai_tax_share")(data) != full
        assert R.reader("tick_host_ms")(data) is not None
    finally:
        data.traced_ticks = []


def test_spans_without_tick_tags_read_nothing(data):
    """A program without the tick's phase spans reports neither metric."""
    from dataclasses import replace
    bare = replace(data, events=[(st, s, e, {k: v for k, v in m.items()
                                             if k != "tick"})
                                 for st, s, e, m in data.events])
    assert R.reader("tick_host_ms")(bare) is None
    assert R.reader("ai_tax_share")(bare) is None
