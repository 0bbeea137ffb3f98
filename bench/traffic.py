"""Open-loop traffic from a mix's data file and a seed.

A mix (``bench/traffic/<name>.json``) fixes the arrival process, its
rate, the length distributions, and the engine's slots and cache. Every
seed gets the same multiset of prompt lengths, output lengths and
inter-arrival gaps, drawn once from the mix's own ``base_seed``; the
run's seed only permutes them and draws the token ids. So two seeds
offer the same work in a different order, and the spread between runs
measures the system, not the draw.

Arrivals are Poisson (exponential gaps, as ``cluster/loadgen.py``'s
open-loop generator draws them) at ``rate_per_s``, optionally scaled by
``bursts``: every ``every_s`` seconds the rate is multiplied by
``factor`` for ``len_s`` seconds. Prompts may open with one of
``shared_prefix.groups`` shared prefixes of ``shared_prefix.len``
tokens, for mixes that exercise prefix reuse.

Arrivals start ``preroll_s`` seconds before the window (default 0), so
that a window that measures a steady state finds the slots as full as
the rate keeps them: those requests are due at negative times.
"""
from __future__ import annotations

import json
import math
import pathlib
from dataclasses import dataclass

import numpy as np

TRAFFIC_DIR = pathlib.Path(__file__).resolve().parent / "traffic"


@dataclass(frozen=True)
class Arrival:
    """One request of the schedule: due ``t_due`` seconds into the window
    (before it, in the pre-roll, where negative)."""
    rid: int
    t_due: float
    prompt: np.ndarray        # (S,) int32 token ids
    max_tokens: int           # generated tokens, the prefill's first included


def load_mix(name: str) -> dict:
    return json.loads((TRAFFIC_DIR / f"{name}.json").read_text())


def _quota(n: int, choices: list[int], weights: list[float]) -> np.ndarray:
    """``n`` values from ``choices`` in proportion to ``weights``,
    largest remainder first: the same multiset for every seed."""
    w = np.asarray(weights, np.float64) / np.sum(weights)
    exact = n * w
    counts = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - counts), kind="stable")[: n - counts.sum()]:
        counts[i] += 1
    return np.repeat(np.asarray(choices, np.int64), counts)


def _lengths(n: int, spec: dict) -> np.ndarray:
    """A fixed multiset of ``n`` lengths from a length spec."""
    if "choices" in spec:
        return _quota(n, spec["choices"], spec["weights"])
    if spec["dist"] == "lognormal":
        # quantiles at the midpoints of n equal slices: a deterministic
        # sample of the distribution, clipped to [min, max]
        from statistics import NormalDist
        z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
        v = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
        return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)
    if spec["dist"] == "uniform":
        v = np.linspace(spec["min"], spec["max"], n)
        return np.rint(v).astype(np.int64)
    raise ValueError(f"unknown length distribution {spec!r}")


def _times(mix: dict, unit_times: np.ndarray) -> np.ndarray:
    """Map cumulative unit-rate arrival times onto the mix's clock: at
    ``rate_per_s``, or in a burst at ``factor`` times that rate."""
    arr = mix["arrivals"]
    if arr["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    r, b = arr["rate_per_s"], arr.get("bursts")
    if not b:
        return unit_times / r
    hi = r * b["factor"] * b["len_s"]           # unit time spent in a burst
    per = hi + r * (b["every_s"] - b["len_s"])  # unit time of one period
    k, rem = np.divmod(unit_times, per)
    return k * b["every_s"] + np.where(
        rem < hi, rem / (r * b["factor"]), b["len_s"] + (rem - hi) / r)


def _segment(mix: dict, base_rng, rng, seconds: float):
    """Due times from the segment's start, prompt lengths and output
    lengths of ``seconds`` of arrivals: exponential unit-rate gaps from
    the mix's base seed, the first n inside the segment, permuted by the
    run's seed with n lengths of each kind. So the count, the last
    arrival and the multisets are the same for every seed."""
    gaps = base_rng.exponential(
        1.0, int(mix["arrivals"]["rate_per_s"] * seconds * 2) + 64)
    n = int(np.searchsorted(_times(mix, np.cumsum(gaps)), seconds))
    return (_times(mix, np.cumsum(rng.permutation(gaps[:n]))),
            rng.permutation(_lengths(n, mix["prompt_len"])),
            rng.permutation(_lengths(n, mix["output_len"])))


def schedule(mix: dict, seed: int, seconds: float, vocab: int) -> list[Arrival]:
    """The pre-roll's and the window's arrivals for ``seed``, in order of
    due time; deterministic in its arguments. The window and the
    pre-roll are drawn apart, so the window offers the same work to
    every seed."""
    base_rng = np.random.default_rng(mix.get("base_seed", 0))
    rng = np.random.default_rng(seed)
    window = _segment(mix, base_rng, rng, seconds)
    pre = mix.get("preroll_s", 0.0)
    before = _segment(mix, base_rng, rng, pre)
    times = np.concatenate([before[0] - pre, window[0]])
    prompts = np.concatenate([before[1], window[1]])
    outputs = np.concatenate([before[2], window[2]])
    n = len(times)
    shared = mix.get("shared_prefix")
    prefixes = (rng.integers(0, vocab, (shared["groups"], shared["len"]),
                             dtype=np.int32) if shared else None)
    out = []
    for i in range(n):
        toks = rng.integers(0, vocab, int(prompts[i]), dtype=np.int32)
        if shared and rng.random() < shared["share"]:
            p = prefixes[rng.integers(shared["groups"])]
            k = min(len(p), len(toks))
            toks[:k] = p[:k]
        out.append(Arrival(i, float(times[i]), toks, int(outputs[i])))
    return out
