"""Median time to first token, from each request's due time, over every
request due in the window (host clock); a request that never got one
ranks above every other."""
import math

from stats import percentile


def read(run):
    v = percentile(run.ttft_s(), 0.50)
    return v * 1e3 if math.isfinite(v) else None
