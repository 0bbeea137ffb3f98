"""Median of the gaps between consecutive output tokens of a request,
over every gap that closed inside the window (host clock): the pace of
a plain scheduler tick, as a reader of a streamed answer sees it."""
from stats import percentile


def read(run):
    gaps = run.itl_s()
    return percentile(gaps, 0.50) * 1e3 if gaps else None
