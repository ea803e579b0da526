"""Benchmark orchestrator — one module per paper table/figure.

Usage:
    PYTHONPATH=src python -m benchmarks.run              # full pass
    PYTHONPATH=src python -m benchmarks.run fig11 fig15  # subset
    BENCH_QUICK=1 PYTHONPATH=src python -m benchmarks.run

Prints ``name,us_per_call,derived`` CSV rows (harness convention); the
roofline benchmark (reads dry-run artifacts) lives in benchmarks/roofline.py
and is included when its inputs exist.
"""

from __future__ import annotations

import sys
import time

from repro.launch.compile_cache import enable_compile_cache

from .common import CsvOut

MODULES = [
    ("fig2", "benchmarks.fig2_vllm_ttft"),
    ("fig5", "benchmarks.fig5_correlation"),
    ("fig9", "benchmarks.fig9_lora_ratio"),
    ("fig11", "benchmarks.fig11_main"),
    ("fig12", "benchmarks.fig12_breakdown"),
    ("fig13", "benchmarks.fig13_hbm_hit"),
    ("fig14", "benchmarks.fig14_alloc_time"),
    ("fig15", "benchmarks.fig15_ablations"),
    ("fig16", "benchmarks.fig16_many_lora"),
    ("overhead", "benchmarks.overhead"),
    ("prefill", "benchmarks.prefill_bench"),
    ("kernels", "benchmarks.kernels_bench"),
    ("roofline", "benchmarks.roofline"),
]


def main() -> None:
    import importlib

    selected = set(sys.argv[1:])
    enable_compile_cache()
    out = CsvOut()
    print("name,us_per_call,derived")
    for name, modpath in MODULES:
        if selected and name not in selected:
            continue
        t0 = time.perf_counter()
        try:
            mod = importlib.import_module(modpath)
            mod.run(out)
        except FileNotFoundError as e:
            print(f"{name}/SKIPPED,0.0,missing_input={e}")
        except Exception as e:  # keep the suite running
            print(f"{name}/ERROR,0.0,{type(e).__name__}:{e}")
            raise
        print(f"# {name} done in {time.perf_counter()-t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
