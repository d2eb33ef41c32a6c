"""Benchmark driver — one section per paper table/figure + the TPU-side
harnesses.  Prints ``name,us_per_call,derived`` CSV (one line per cell).

  table2    — Table II   (transpose × 8 memories × 3 sizes)
  table3    — Table III  (FFT radix 4/8/16 × 9 memories, func-verified)
  table1    — Table I    (area model / sector footprints)
  fig9      — Fig 9      (cost vs performance crossover)
  autotune  — repro.tune re-derives the paper's per-workload winners
  serving   — paged-KV serving traffic × 9 memories (docs/SERVING.md)
  cost      — batched cost engine vs per-arch loop (writes BENCH_cost.json)
  kernels   — Pallas kernel micro-bench
  roofline  — §Roofline terms from dry-run artifacts (if present)
"""
from __future__ import annotations

import os
import sys

# script-style execution (`python benchmarks/run.py`) puts benchmarks/ on
# sys.path, not the repo root the package imports need
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    sections = sys.argv[1:] or ["table2", "table3", "table1", "fig9",
                                "autotune", "serving", "cost", "beyond",
                                "bankscale", "kernels", "roofline"]
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (autotune, bank_scaling, beyond_paper, cost_bench,
                            fig9_cost_perf, kernel_bench, roofline_report,
                            serving_bench, table1_area, table2_transpose,
                            table3_fft)
    mods = {"table2": table2_transpose, "table3": table3_fft,
            "table1": table1_area, "fig9": fig9_cost_perf,
            "autotune": autotune, "serving": serving_bench,
            "cost": cost_bench, "beyond": beyond_paper,
            "bankscale": bank_scaling, "kernels": kernel_bench,
            "roofline": roofline_report}
    for s in sections:
        print(f"# --- {s} ---")
        mods[s].main()


if __name__ == "__main__":
    main()
