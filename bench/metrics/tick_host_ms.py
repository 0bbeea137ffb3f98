"""Host time of a plain scheduler tick, in ms, with the profiler off.

The engine tags each span of a continuous tick with ``tick``: its host
phases (``pre_*``, ``post_*``), its timed transfers (the upload and the
readback of the decode step, a prefill's token read), and its device
waits (``prefill``, ``decode``). A tick's host time is the sum of its
host-phase and transfer spans. The value is the median of that sum over
the window's ticks that ended before the traced span began, where the
profiler is off. A program whose spans carry no ``tick`` reads nothing.
"""
from collections import defaultdict

from stats import percentile


def untraced_end(run) -> float:
    """Where the traced part of the window begins (its end if none)."""
    return min((t.t0 for t in run.traced_ticks), default=run.window_s)


def read(run):
    end = untraced_end(run)
    host, extent = defaultdict(float), {}
    for stage, s, e, meta in run.events:
        n = meta.get("tick")
        if n is None:
            continue
        lo, hi = extent.get(n, (s, e))
        extent[n] = (min(lo, s), max(hi, e))
        if stage.startswith(("pre_", "post_")) or stage == "transfer":
            host[n] += e - s
    vals = [host[n] for n, (lo, hi) in extent.items() if lo >= 0 and hi < end]
    return percentile(vals, 0.50) * 1e3 if vals else None
