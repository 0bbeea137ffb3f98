"""93rd percentile of the gaps between consecutive output tokens of a
request, over every gap that closed inside the window (host clock): a
tick that also prefilled a newly admitted prompt, as every busy slot's
reader sees it.

The gaps fall in modes: plain ticks, and ticks that also ran a prefill
of one of the mix's prompt lengths. In every cell measured this
percentile lies inside one prefill mode with room on both sides, where
the 90th, 95th and 98th lie on an edge between two and flip between
them from run to run."""
from stats import percentile


def read(run):
    gaps = run.itl_s()
    return percentile(gaps, 0.93) * 1e3 if gaps else None
