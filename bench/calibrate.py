#!/usr/bin/env python3
"""Readings that the limit of a cell's comparison is set from, in one
process on the chip:

    python3 bench/calibrate.py --workload sd35-1024-backlog --seconds 10 \\
        --seeds 11,12,13 --control-seeds 11,12,13

For each seed, one run of the cell exactly as ``run.py`` makes it, with a
shorter window: weights from the seed, the cell's traffic at its own load,
the window's answers compared with the reference. For the control seeds the
same sample is also computed by the reference in float8 e4m3, and that
control's gap from the reference is read. Prints one JSON line per seed.
"""
import argparse
import gc
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402


def _seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    harness.pin_compile_cache()
    cell = harness.load_cell(args.workload, BENCH.parent)
    control = set(_seeds(args.control_seeds))
    for seed in _seeds(args.seeds):
        t0 = time.perf_counter()
        res = harness.run_cell(cell, seed, args.seconds, False, t_start=t0,
                               control=seed in control)
        print(json.dumps({
            "seed": seed, "correct": res["correct"],
            "latent_rel_err_max": res["checks"]["latent_rel_err_max"]["value"],
            "control_rel_err_max": res.get("control_rel_err_max"),
            "attempted": res["attempted"], "sampled": res["sampled"],
            "compiles_in_window": res["compiles_in_window"],
            "memory_peak_bytes": res["device"]["memory_peak_bytes"],
            "reference_s": res["reference_s"],
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "wall_s": time.perf_counter() - t0,
        }), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
