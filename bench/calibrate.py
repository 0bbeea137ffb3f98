"""Readings that set a cell's rate and its correctness limit, on the chip.

    python3 bench/calibrate.py sweep  --workload <cell> --rates 1,2,3 --seconds 40
    python3 bench/calibrate.py limits --workload <cell> --seeds 12 --control 3 --seconds 51
    python3 bench/calibrate.py fixture --out <dir>

``sweep`` runs the cell's mix, pre-roll included, at each rate and
prints per rate the tails, tokens/s, and the slots' occupancy and the
queue for a slot in each quarter of the window: a queue that keeps
growing marks a rate above the knee. ``limits`` runs the program on
each seed at the cell's own load and window and reads the mean logit
gap of what it served (the lower reading); on the first ``--control``
seeds it also puts the int8 and the fp8 control in the program's place
over the same sample and passes them through the run's own verdict
(the upper reading). ``fixture`` records a small profiler trace through
the harness, for the trace reducer's test. One JSON object per reading
goes to standard output. None of this runs in a benchmark run.
"""
from __future__ import annotations

import argparse
import copy
import json
import statistics
import sys
import time

import numpy as np

import run as R


def _cell(spec, name):
    cell = next(w for w in spec["workloads"] if w["name"] == name)
    entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    return cell, json.loads((R.ROOT / entry["file"]).read_text())


def _drive(cell, config, seed, seconds, mix, compiles, trace=False,
           trace_dir=None):
    from drivers import lm_serving as D
    return D.run(cell, config, seed, seconds, trace, t_start=time.perf_counter(),
                 limits={"mean_logit_gap": float("inf")}, peaks={},
                 trace_dir=trace_dir, compiles=compiles, mix=mix,
                 keep_trace=trace)


def _quarters(ticks, seconds: float, value) -> list[float]:
    """Mean of ``value(tick)`` over the ticks of each quarter of the window."""
    out = []
    for q in range(4):
        lo, hi = q * seconds / 4, (q + 1) * seconds / 4
        vs = [value(t) for t in ticks if lo <= t.t0 < hi]
        out.append(statistics.fmean(vs) if vs else 0.0)
    return out


def sweep(args, spec, compiles):
    """Per rate: the tails, tokens/s, and how the slots' occupancy and
    the queue for a slot moved through the window's quarters. A queue
    that keeps growing marks a rate above the knee."""
    from stats import percentile
    cell, config = _cell(spec, args.workload)
    import traffic
    base = traffic.load_mix(cell["traffic"])
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = copy.deepcopy(base)
        mix["arrivals"]["rate_per_s"] = rate
        out = _drive(cell, config, args.seed, args.seconds, mix, compiles)
        d = out.data
        ttft, itl = sorted(d.ttft_s()), d.itl_s()
        line = {"rate": rate, "requests": out.attempted,
                "ttft_p50_ms": 1e3 * percentile(ttft, 0.5),
                "ttft_p95_ms": 1e3 * percentile(ttft, 0.95),
                "itl_p50_ms": 1e3 * percentile(itl, 0.5),
                "itl_p95_ms": 1e3 * percentile(itl, 0.95),
                "tokens_per_s": d.tokens_in_window() / d.window_s,
                "busy_slots_by_quarter": _quarters(
                    d.ticks, d.window_s, lambda t: len(t.decode_kv_lens)),
                "queued_by_quarter": _quarters(
                    d.ticks, d.window_s, lambda t: t.queued),
                "ticks": sum(t.t0 >= 0 for t in d.ticks), "log": out.log}
        print(json.dumps(line), flush=True)


def limits(args, spec, compiles):
    """The mean logit gap of the program on each seed at the cell's load
    and window (the lower reading) and, on the first ``--control``
    seeds, of the int8 and fp8 controls over the same sample (the upper
    reading), each also through the run's own verdict."""
    from drivers import lm_serving as D
    cell, config = _cell(spec, args.workload)
    import traffic
    mix = traffic.load_mix(cell["traffic"])
    lim = json.loads((R.BENCH / "limits" / f"{args.workload}.json").read_text())
    lim = {k: v["limit"] for k, v in lim.items()}
    seeds = [args.seed + 7919 * k for k in range(args.seeds)]
    for k, seed in enumerate(seeds):
        out = _drive(cell, config, seed, args.seconds, None, compiles)
        line = {"workload": args.workload, "seed": seed,
                "checks": out.checks, "correct": out.correct,
                "served_tokens": sum(len(c.tokens) for c in out.sample),
                "requests_checked": len(out.sample), "log": out.log}
        t = time.perf_counter()
        for low in ("int8", "fp8") if k < args.control else ():
            gaps = D.reference_gaps(D.as_run(config), seed, mix, out.sample, low)
            checks, correct = D.verdict(out.data.clients, out.sample, gaps,
                                        lim, mix["check"])
            line[low] = {"mean_logit_gap": float(gaps.mean()),
                         "max_logit_gap": float(gaps.max()),
                         "mismatch": float(np.mean(gaps > 0)), "correct": correct}
        line["controls_s"] = time.perf_counter() - t
        print(json.dumps(line), flush=True)


def fixture(args, spec, compiles):
    """A one-second traced run of a two-layer granite at full width."""
    import pathlib
    import shutil
    cell, config = _cell(spec, "granite-chat")
    config = dict(config, num_hidden_layers=2)
    import traffic
    mix = dict(traffic.load_mix(cell["traffic"]), slots=4, cache_len=512,
               preroll_s=0.0,
               arrivals={"process": "poisson", "rate_per_s": 20.0},
               prompt_len={"choices": [64, 128], "weights": [1, 1]},
               output_len={"dist": "uniform", "min": 4, "max": 8})
    from drivers import lm_serving as D
    D.TRACE_SECONDS = 0.5
    tdir = pathlib.Path(args.out) / "raw"
    out = _drive(cell, config, args.seed, 1.0, mix, compiles, trace=True,
                 trace_dir=tdir)
    pb = sorted(tdir.rglob("*.xplane.pb"))[-1]
    shutil.copy(pb, pathlib.Path(args.out) / "small.xplane.pb")
    s = out.data.trace
    print(json.dumps({"busy_s": s.busy_s, "window_s": s.window_s,
                      "module_s": s.module_s, "module_n": s.module_n,
                      "traced_ticks": len(out.data.traced_ticks),
                      "prefill_tokens": sum(sum(t.prefill_lens) for t in out.data.traced_ticks),
                      "decode_steps": sum(1 for t in out.data.traced_ticks if t.decode_kv_lens),
                      "device_ops": s.device_ops[:3], "idle_gaps": s.idle_gaps[:3]}))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("mode", choices=("sweep", "limits", "fixture"))
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=2 ** 31 + 11)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--out", default="chiprun_out/fixture")
    args = ap.parse_args(argv)
    jax = R.configure_jax()
    if jax.devices()[0].platform == "cpu":
        print("calibrate.py: needs the chip", file=sys.stderr)
        return 2
    compiles = R.CompileCounter()
    {"sweep": sweep, "limits": limits, "fixture": fixture}[args.mode](
        args, R.load_spec(), compiles)
    return 0


if __name__ == "__main__":
    sys.exit(main())
