"""The trace reduction, on a built trace and on one recorded on a v5e."""
import gzip
import json
import shutil

import pytest

import trace as T
from conftest import BENCH

FIXTURE = BENCH / "tests" / "data"


def _built():
    tr = T.Trace()
    tr.host = [(T.TRACED, 1.0, 2.0), ("bench.tick", 1.0, 1.5),
               ("PjitFunction(_prefill)", 1.05, 1.06), ("bench.idle", 1.5, 2.0)]
    tr.modules = [("jit__prefill", 1.1, 1.2), ("jit__step_batched_fused", 1.3, 1.45),
                  ("jit__prefill", 0.5, 0.6)]
    tr.ops = [("", "fusion.1 fusion f32[8]", 1.1, 1.15), ("", "fusion.2 fusion f32[8]", 1.12, 1.2),
              ("", "while.3 while (tuple)", 1.3, 1.4), ("", "fusion.4 fusion f32[8]", 1.3, 1.4),
              ("", "fusion.9 fusion f32[8]", 0.5, 0.6)]
    T._attribute_ops(tr)
    return tr


def test_union_merges_overlaps():
    assert T.union([(3, 4), (1, 2), (1.5, 2.5), (2.5, 2.6)]) == [(1, 2.6), (3, 4)]


def test_summary_of_a_built_trace():
    s = T.summarize(_built())
    assert s.window_s == pytest.approx(1.0)
    assert s.busy_s == pytest.approx(0.1 + 0.1)
    assert s.module_s["jit__prefill"] == pytest.approx(0.1)     # the one in span
    assert s.module_n == {"jit__prefill": 1, "jit__step_batched_fused": 1}
    ops = dict(s.device_ops)
    assert "jit__step_batched_fused: while.3 while (tuple)" not in ops
    assert ops["jit__step_batched_fused: fusion.4 fusion f32[8]"] == pytest.approx(0.1)
    idle = dict(s.idle_gaps)
    # in the tick: 1.0-1.05, 1.06-1.1, 1.2-1.3, 1.45-1.5; dispatching the
    # prefill 1.05-1.06; waiting for arrivals 1.5-2.0; inside the decode
    # program, after its loop, 1.4-1.45
    assert idle["bench.tick"] == pytest.approx(0.24)
    assert idle["in jit__step_batched_fused"] == pytest.approx(0.05)
    assert idle["PjitFunction(_prefill)"] == pytest.approx(0.01)
    assert idle["bench.idle"] == pytest.approx(0.5)
    assert sum(idle.values()) == pytest.approx(s.window_s - s.busy_s)


def test_a_recorded_trace(tmp_path):
    """A 0.5 s traced span of a two-layer granite at full width, served
    through the harness on one v5e (``calibrate.py fixture``)."""
    pb = tmp_path / "small.xplane.pb"
    with gzip.open(FIXTURE / "small.xplane.pb.gz") as src, open(pb, "wb") as dst:
        shutil.copyfileobj(src, dst)
    want = json.loads((FIXTURE / "small.json").read_text())
    s = T.summarize(T.load(pb))
    assert s.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert s.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0 < s.busy_s < s.window_s
    assert s.module_n == want["module_n"]
    assert s.module_n["jit__step_batched_fused"] == want["decode_steps"]
    assert sum(v for _, v in s.idle_gaps) <= s.window_s - s.busy_s + 1e-9
    assert len(s.device_ops) == 10 and len(s.idle_gaps) <= 10


def test_per_layer_readers_on_a_built_summary():
    import flops
    import run as R
    import smoke
    from drivers.lm_serving import RunData, Tick
    c = smoke.config("granite-moe-3b-a800m")
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    ticks = [Tick(1.0, 1.1, [16], [17, 30]), Tick(1.1, 1.2, [], [18, 31]),
             Tick(1.2, 1.3, [], [])]
    s = T.Summary(window_s=2.0, busy_s=0.5,
                  module_s={"jit__prefill": 0.016, "jit__step_batched_fused": 0.1},
                  module_n={"jit__prefill": 1, "jit__step_batched_fused": 2},
                  device_ops=[], idle_gaps=[])
    d = RunData(c, {}, peaks, 10.0, 1.0, [], ticks, [], s, ticks)
    read = lambda name: R.reader(name)(d)
    assert read("prefill_device_ms_per_ktok") == pytest.approx(1000.0)
    assert read("decode_step_device_ms") == pytest.approx(50.0)
    assert read("device_idle_share") == pytest.approx(75.0)
    least = [max(flops.decode_flops(c, k) / 1e12, flops.decode_bytes(c, k) / 1e9)
             for k in ([17, 30], [18, 31])]
    assert read("decode_step_roofline") == pytest.approx(100 * sum(least) / 2 / 0.05)
    ops = (flops.decode_flops(c, [17, 30]) + flops.decode_flops(c, [18, 31])) / 2
    assert read("mfu.decode") == pytest.approx(100 * ops / (0.05 * 1e12))
    # a run without a trace reads nothing
    d.trace = None
    assert read("decode_step_roofline") is None and read("mfu.decode") is None
