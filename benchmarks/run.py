"""Benchmark harness: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run --only fig9

Output: CSV lines ``bench,metric,value,claim,OK|FAIL``; exit status 1 if
any paper claim fails.  Artifacts land in artifacts/benchmarks/.
"""
from __future__ import annotations

import argparse
import sys
import time

from benchmarks import (
    fig2_model_pool,
    fig4_constant_load,
    fig5_fig6_schedulers,
    fig7_traces,
    fig8_burst_sizing,
    fig9_paragon,
    rl_vs_schemes,
    roofline,
    scenario_grid,
    sim_throughput,
    spot_tier,
    tier_portfolio,
    variant_grid,
)
from repro.launch.compile_cache import enable_compile_cache

BENCHES = {
    "fig2": fig2_model_pool.run,
    "fig4": fig4_constant_load.run,
    "fig5_fig6": fig5_fig6_schedulers.run,
    "fig7": fig7_traces.run,
    "fig8": fig8_burst_sizing.run,
    "fig9": fig9_paragon.run,
    "rl": rl_vs_schemes.run,
    "spot": spot_tier.run,
    "roofline": roofline.run,
    "scenario_grid": scenario_grid.run,
    "sim_throughput": sim_throughput.run,
    "tier_portfolio": tier_portfolio.run,
    "variant_grid": variant_grid.run,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=list(BENCHES))
    args = ap.parse_args()
    enable_compile_cache()

    t0 = time.perf_counter()
    print("bench,metric,value,claim,status")
    ok = True
    for name, fn in BENCHES.items():
        if args.only and name != args.only:
            continue
        try:
            ok &= fn()
        except Exception as e:  # noqa: BLE001
            print(f"{name},_error,0,{type(e).__name__}: {e},FAIL")
            ok = False
    print(f"all,_total_wall_s,{time.perf_counter() - t0:.1f},,"
          f"{'OK' if ok else 'FAIL'}")
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
