"""Reduce a profiler trace (``.xplane.pb``) to device time.

The harness wraps the traced part of its window in a host span named
``bench.traced`` and each scheduler tick, submission and idle wait in
spans of their own (``bench.tick``, ``bench.submit``, ``bench.idle``).
From the trace this module takes, on the first TPU device plane, the
program executions (line ``XLA Modules``) and the operations (line
``XLA Ops``), and on the host the spans of the thread that ran the
window (the line holding ``bench.traced``). All times are clipped to the ``bench.traced`` span.

Busy time is the union of operation intervals; idle time is the rest of
the span. A gap inside a program's execution is charged to that
program; each stretch of a gap between programs to the innermost host
span that covers it.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from dataclasses import dataclass, field

TRACED = "bench.traced"
_HASH = re.compile(r"\(\d+\)$")


@dataclass
class Trace:
    """Plain events of one trace, times in seconds on the trace's clock."""
    modules: list = field(default_factory=list)   # (name, start, end)
    ops: list = field(default_factory=list)       # (module, op, start, end)
    host: list = field(default_factory=list)      # (name, start, end)


def load(path: str, device_plane: str = "/device:TPU:0") -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    tr = Trace()
    for plane in pd.planes:
        if plane.name == device_plane:
            for line in plane.lines:
                if line.name == "XLA Modules":
                    tr.modules = [(_HASH.sub("", e.name), e.start_ns * 1e-9,
                                   (e.start_ns + e.duration_ns) * 1e-9)
                                  for e in line.events]
                elif line.name == "XLA Ops":
                    tr.ops = [("", _op_name(e.name), e.start_ns * 1e-9,
                               (e.start_ns + e.duration_ns) * 1e-9)
                              for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                events = [(e.name, e.start_ns * 1e-9,
                           (e.start_ns + e.duration_ns) * 1e-9)
                          for e in line.events]
                if any(n == TRACED for n, _, _ in events):
                    tr.host = events
    _attribute_ops(tr)
    return tr


def _op_name(text: str) -> str:
    """``%fusion.5 = bf16[64,1536]{...} fusion(...)`` -> ``fusion.5 fusion bf16[64,1536]``."""
    m = re.match(r"%?(\S+) = (.*?)\s([a-z][a-z0-9-]*)\(", text)
    if not m:
        return text[:80]
    shape = m.group(2) if m.group(2)[0] != "(" else "(tuple)"
    return f"{m.group(1)} {m.group(3)} {re.sub(r'\{.*', '', shape)}"


# ops that only contain others: their time is their body's
_CONTAINERS = (" while ", " conditional ", " call ")


def _attribute_ops(tr: Trace) -> None:
    """Name each op's program by the module execution that contains it."""
    mods = sorted(tr.modules, key=lambda m: m[1])
    starts = [m[1] for m in mods]
    out = []
    for _, op, s, e in tr.ops:
        i = bisect.bisect_right(starts, s) - 1
        mod = mods[i][0] if i >= 0 and s < mods[i][2] else "?"
        out.append((mod, op, s, e))
    tr.ops = out


def traced_span(tr: Trace) -> tuple[float, float]:
    spans = [(s, e) for n, s, e in tr.host if n == TRACED]
    if len(spans) != 1:
        raise ValueError(f"expected one {TRACED!r} host span, found {len(spans)}")
    return spans[0]


def _clip(intervals, t0, t1):
    return [(max(s, t0), min(e, t1)) for s, e in intervals if e > t0 and s < t1]


def union(intervals) -> list[tuple[float, float]]:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


@dataclass
class Summary:
    """What the per-layer readers take from a trace."""
    window_s: float
    busy_s: float
    module_s: dict          # program name -> device seconds in the span
    module_n: dict          # program name -> executions starting in the span
    device_ops: list        # [(name, seconds)] most time first, at most 10
    idle_gaps: list         # [(host span, idle seconds)] most first, at most 10


def summarize(tr: Trace) -> Summary:
    t0, t1 = traced_span(tr)
    busy = union(_clip([(s, e) for _, _, s, e in tr.ops], t0, t1))
    busy_s = sum(e - s for s, e in busy)
    module_s, module_n = defaultdict(float), defaultdict(int)
    for name, s, e in tr.modules:
        if t0 <= s < t1:
            module_s[name] += min(e, t1) - s
            module_n[name] += 1
    per_op = defaultdict(float)
    for mod, op, s, e in tr.ops:
        if any(c in f" {op} " for c in _CONTAINERS):
            continue
        for a, b in _clip([(s, e)], t0, t1):
            per_op[f"{mod}: {op}"] += b - a
    gaps, prev = [], t0
    for s, e in busy:
        if s > prev:
            gaps.append((prev, s))
        prev = e
    if prev < t1:
        gaps.append((prev, t1))
    idle = defaultdict(float)
    hosts = sorted((s, e, n) for n, s, e in tr.host if n != TRACED)
    mods = sorted((s, e, n) for n, s, e in tr.modules)
    mod_starts = [m[0] for m in mods]
    for s, e in gaps:
        # inside a program's execution the stall is the device's own;
        # between programs it is charged to what the host was doing
        j = max(0, bisect.bisect_right(mod_starts, s) - 1)
        for ms, me, mname in mods[j:bisect.bisect_left(mod_starts, e)]:
            a, b = max(s, ms), min(e, me)
            if a >= b:
                continue
            idle[f"in {mname}"] += b - a
            for x, y, name in _segments(hosts, s, a):
                idle[name] += y - x
            s = b
        for x, y, name in _segments(hosts, s, e):
            idle[name] += y - x
    top = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:10]
    return Summary(t1 - t0, busy_s, dict(module_s), dict(module_n),
                   [list(x) for x in top(per_op)], [list(x) for x in top(idle)])


def _segments(hosts, s: float, e: float):
    """Split [s, e) where host spans begin or end; label each piece by
    the shortest host span covering it, or ``none``."""
    i = bisect.bisect_left(hosts, (e,))
    near = [h for h in hosts[max(0, i - 400):i] if h[1] > s]
    if e <= s:
        return []
    cuts = sorted({s, e} | {t for a, b, _ in near for t in (a, b) if s < t < e})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        mid = 0.5 * (a + b)
        cover = [(hb - ha, n) for ha, hb, n in near if ha <= mid < hb]
        out.append((a, b, min(cover)[1] if cover else "none"))
    return out
