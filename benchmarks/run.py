"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Run:
    PYTHONPATH=src python -m benchmarks.run [--only fig10]
"""
from __future__ import annotations

import argparse
import importlib
import sys
import traceback

from repro.launch.compile_cache import enable_compile_cache

MODULES = [
    "benchmarks.fig05_core_scaling",
    "benchmarks.fig06_latency_breakdown",
    "benchmarks.fig08_cpu_breakdown",
    "benchmarks.fig09_amdahl",
    "benchmarks.fig10_acceleration",
    "benchmarks.fig11_bandwidth",
    "benchmarks.fig14_object_detection",
    "benchmarks.fig15_unlocking",
    "benchmarks.fig_batching_sweep",
    "benchmarks.fig_cluster_scaling",
    "benchmarks.fig_decode_batching",
    "benchmarks.fig_fault_recovery",
    "benchmarks.fig_fused_path",
    "benchmarks.fig_preprocess_offload",
    "benchmarks.fig_reliability",
    "benchmarks.fig_roofline_sweep",
    "benchmarks.fig_scenarios",
    "benchmarks.tab34_tco",
    "benchmarks.roofline_table",
    "benchmarks.kernel_bench",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on module names")
    args = ap.parse_args()
    enable_compile_cache()
    print("name,us_per_call,derived")
    failures = 0
    for modname in MODULES:
        if args.only and args.only not in modname:
            continue
        try:
            mod = importlib.import_module(modname)
            for line in mod.run():
                print(line)
        except Exception:  # noqa: BLE001 — report all benches
            failures += 1
            traceback.print_exc()
            print(f"{modname},0.0,ERROR")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
