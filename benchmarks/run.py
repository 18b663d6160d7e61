"""Benchmark harness entry: one section per paper table/figure.

Prints ``name,...`` CSV lines.  Sections:
  analytic_model  -- Fig. 2 (memory/FLOPs model; validates the paper's 21.3% /
                     56.2% numbers exactly)
  kernel_bench    -- Fig. 4 (CPU interpret timings + v5e roofline projection)
  e2e_bench       -- Fig. 5/6 (real reduced-model train/prefill wall time)
  ablation        -- Fig. 9 (early-return / group-fold ablations)
  roofline        -- Roofline terms from the dry-run artifacts (if present)
"""
from __future__ import annotations

import traceback


def _section(name, fn):
    print(f"# --- {name} ---")
    try:
        fn()
    except Exception as e:  # noqa: BLE001 -- benchmarks are independent
        print(f"{name},ERROR,{type(e).__name__}: {e}")
        traceback.print_exc()


def main() -> None:
    from benchmarks import (ablation_bench, analytic_model, e2e_bench,
                            kernel_bench, roofline)

    _section("analytic_model", analytic_model.main)
    _section("kernel_bench", kernel_bench.main)
    _section("e2e_bench", e2e_bench.main)
    _section("ablation_bench", ablation_bench.main)
    _section("roofline", roofline.main)


if __name__ == '__main__':
    main()
