"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Every row also *asserts*
the paper's corresponding claim (tolerances documented inline), so this
doubles as the reproduction gate:

  fig7_sqnr       — Fig. 7  SQNR vs (B_A, B_X, N, coding, sparsity)
  fig8_bandwidth  — Fig. 8  C_x/C_y/C_CIMU, utilization, A-load cycles
  fig10_transfer  — Fig. 10 column transfer functions + multi-bit match
  fig11_networks  — Fig. 11 network demos + summary/comparison headline
  kernels_bench   — Pallas kernel tiles: VMEM footprint, arith intensity
  accel_bench     — backend parity/cost through the repro.accel API
"""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from . import (accel_bench, fig7_sqnr, fig8_bandwidth, fig10_transfer,
                   fig11_networks, kernels_bench)

    print("name,us_per_call,derived")
    failures = []
    for mod in (fig8_bandwidth, fig11_networks, fig10_transfer, fig7_sqnr,
                kernels_bench, accel_bench):
        try:
            mod.run()
        except Exception: 
            failures.append(mod.__name__)
            traceback.print_exc()
    if failures:
        print(f"BENCH FAILURES: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    main()
