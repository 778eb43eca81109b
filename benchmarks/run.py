"""Benchmark harness — one section per paper table/figure.

Emits ``name,us_per_call,derived`` CSV lines:
  table3/*        Table 3  (template complexity — exact reproduction)
  fig6/*          Fig. 6   (template-size scaling, single node)
  spmm/*, color_combine/*, fused/*, iter/*
                  kernel-level hot-path benchmarks (bench_kernels); also
                  written machine-readable to BENCH_kernels.json at the
                  repo root — the per-PR perf trajectory record
  strong/*        Fig. 7/9/15 (strong scaling, naive vs pipeline vs adaptive)
  weak/*          Fig. 10  (weak scaling)
  fig11/*         Fig. 11  (load balance vs skew; task-size effects)
  peakmem/*       Fig. 12  (peak memory: naive vs pipeline vs ring)
  overall/*       Fig. 13  (end-to-end, naive vs adaptive, template sweep)
  multi_template/* family counting: shared-DAG reuse vs independent passes
                  (bench_multi_template; BENCH_multi_template.json)
  adaptive_policy/*, lm_coll/*  (beyond paper: LM collectives)

Multi-device sections run in subprocesses with 8 host devices; the main
process keeps a single device.  A failed section does not stop the
others, but the harness then exits non-zero.
"""

from __future__ import annotations

import sys
import traceback

from . import bench_kernels, bench_load_balance, bench_multi_template, bench_templates
from .common import run_worker


def _section(name, fn, failed: list) -> None:
    print(f"# --- {name} ---", flush=True)
    try:
        fn()
    except Exception as e:  # noqa: BLE001 — run the other sections, fail at exit
        traceback.print_exc()
        print(f"{name}/FAILED,0.0,{type(e).__name__}", flush=True)
        failed.append(name)


def main() -> None:
    failed: list = []
    _section("templates", bench_templates.run, failed)
    _section("kernels", bench_kernels.run, failed)
    _section("load_balance", bench_load_balance.run, failed)
    _section("multi_template", bench_multi_template.run, failed)
    _section(
        "strong_scaling",
        lambda: print(
            run_worker("benchmarks._scaling_worker", ["strong", "--template", "u5-2"]),
            end="",
        ),
        failed,
    )
    _section(
        "weak_scaling",
        lambda: print(
            run_worker("benchmarks._scaling_worker", ["weak", "--template", "u5-2"]),
            end="",
        ),
        failed,
    )
    _section(
        "peak_memory",
        lambda: print(
            run_worker("benchmarks._scaling_worker", ["peakmem", "--template", "u7-2"]),
            end="",
        ),
        failed,
    )
    _section(
        "overall",
        lambda: print(run_worker("benchmarks._scaling_worker", ["overall"]), end=""),
        failed,
    )

    from . import bench_lm_collectives

    _section("lm_collectives", bench_lm_collectives.run, failed)
    if failed:
        sys.exit("failed sections: " + ", ".join(failed))


if __name__ == "__main__":
    main()
