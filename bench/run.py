"""Benchmark harness: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the cell's chips.
Everything is found by name from ``BENCHMARK.json``: the cell names a
configuration (``bench/configs/<name>.json``, whose ``kind`` picks the
driver ``bench/drivers/<kind>.py``) and a traffic mix
(``bench/traffic/<name>.json``); each metric is read by
``bench/metrics/<metric>.py``; the limits of the correctness check are
``bench/limits/<cell>.json``; device peaks are ``bench/peaks.json``.
So a configuration, a mix, a metric or a cell is added by adding files.

With ``--trace 0`` the result carries the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics, read from a profiler trace of
the end of the window. The last line of standard output is one JSON
object; the last lines of standard error give each number compared
beside its limit. Without an accelerator, or with fewer chips than the
cell asks for, it exits with code 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()   # process start, for setup_s

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"          # fixed: the path is part of the key
TRACE_DIR = ROOT / ".bench_trace"


class CompileCounter:
    """Programs lowered (each new function or shape) and backend compile
    seconds, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.lowered = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowered += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs


def load_spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell_metrics(spec: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports: end-to-end without tracing,
    per-layer with it; a metric with ``workloads`` only in those cells."""
    ms = spec["per_layer" if trace else "end_to_end"]
    return [m for m in ms if cell in m.get("workloads", [cell])]


def reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def configure_jax():
    """The persistent compilation cache lives in the checkout, at one
    fixed path, and takes every program."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for p in (ROOT / "src", BENCH):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax


def execute(spec: dict, workload: str, seed: int, seconds: float, trace: bool,
            *, devices, compiles, t_start: float) -> tuple[dict, list[str]]:
    """Run one cell on ``devices``; returns the result line's object and
    the lines for standard error, the checks last."""
    cell = next(w for w in spec["workloads"] if w["name"] == workload)
    entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    config = json.loads((ROOT / entry["file"]).read_text())
    dev = devices[0]
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if dev.platform != "cpu" and dev.device_kind not in table:
        raise KeyError(f"no peaks for device kind {dev.device_kind!r} in peaks.json")
    peaks = table.get(dev.device_kind, {})
    limits = json.loads((BENCH / "limits" / f"{workload}.json").read_text())
    limits = {k: v["limit"] for k, v in limits.items()}
    driver = importlib.import_module(f"drivers.{config['kind']}")
    out = driver.run(cell, config, seed, seconds, trace, t_start=t_start,
                     limits=limits, peaks=peaks,
                     trace_dir=TRACE_DIR / f"{workload}-{os.getpid()}",
                     compiles=compiles)
    metrics = {}
    for m in cell_metrics(spec, workload, trace):
        v = reader(m["name"])(out.data)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": out.memory_peak_bytes}
    line = {"correct": out.correct, "attempted": out.attempted,
            "failed": out.failed, "metrics": metrics, "device": device}
    if trace and out.data.trace is not None:
        s = out.data.trace
        device["busy_s"], device["window_s"] = s.busy_s, s.window_s
        line["breakdown"] = {"device_ops": s.device_ops, "idle_gaps": s.idle_gaps}
    line["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in out.checks}
    err = out.log + [f"setup_s {out.data.setup_s:.3f}, backend compile "
                     f"{compiles.compile_s:.3f} s"]
    err += [f"check {n} {v} limit {lim}" for n, v, lim in out.checks]
    return line, err


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_spec()
    cell = next((w for w in spec["workloads"] if w["name"] == args.workload), None)
    if cell is None:
        print(f"run.py: no workload {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    jax = configure_jax()
    devices = jax.devices()
    if devices[0].platform == "cpu" or len(devices) < cell["chips"]:
        print(f"run.py: {args.workload} needs {cell['chips']} accelerator "
              f"chip(s); JAX found {len(devices)} {devices[0].platform} "
              "device(s)", file=sys.stderr)
        return 2
    compiles = CompileCounter()
    line, err = execute(spec, args.workload, args.seed, args.seconds,
                        bool(args.trace), devices=devices[:cell["chips"]],
                        compiles=compiles, t_start=T_START)
    for e in err:
        print(e, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
