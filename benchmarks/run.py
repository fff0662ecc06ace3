"""Benchmark driver — one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default="")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    from benchmarks import (fig2_latent_trajectory, fig5_relay_step_sweep,
                            fig6_scheduler_comparison, roofline,
                            table3_relay_quality, table4_ablation)

    benches = {
        "fig2": fig2_latent_trajectory.run,
        "table3": table3_relay_quality.run,
        "fig5": fig5_relay_step_sweep.run,
        "fig6": fig6_scheduler_comparison.run,
        "table4": table4_ablation.run,
        "roofline": roofline.run,
    }
    print("name,us_per_call,derived")
    failed = []
    for name, fn in benches.items():
        if args.only and name != args.only:
            continue
        try:
            fn(quick=args.quick)
        except Exception as e:  # report and continue
            failed.append(name)
            traceback.print_exc()
            print(f"{name},0.0,ERROR={type(e).__name__}")
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
