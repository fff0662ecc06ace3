#!/usr/bin/env python3
"""Record the small chip trace that ``tests/test_bench_trace.py`` reduces:

    python3 bench/record_fixture.py --out trace_small.xplane.pb

Runs the tiny test configuration (``tests/data/tiny-relay.json`` under
``tests/data/tiny-steady.json``) for half a second with ``--trace 1`` on the
chip, keeps its ``.xplane.pb``, and prints the trace's planes and lines and
the reduction. The test reads it gzipped, as
``tests/data/trace_small.xplane.pb.gz``.
"""
import argparse
import json
import sys
import time
from collections import Counter
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import trace_reduce  # noqa: E402

DATA = BENCH / "tests" / "data"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", type=Path, required=True)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args(argv)

    harness.pin_compile_cache()
    cell = harness.Cell(
        "tiny-steady", 1, json.loads((DATA / "tiny-relay.json").read_text()),
        json.loads((DATA / "tiny-steady.json").read_text()), [], [])
    res = harness.run_cell(cell, args.seed, 0.5, True,
                           t_start=time.perf_counter(), keep_trace=args.out)
    print(json.dumps({k: res[k] for k in ("correct", "attempted", "device")}))

    import jax

    data = jax.profiler.ProfileData.from_file(str(args.out))
    for plane in data.planes:
        lines = {line.name: len(list(line.events)) for line in plane.lines}
        print(f"plane {plane.name!r}: {len(lines)} lines {lines if len(lines) < 12 else sorted(lines)[:12]}")
        for line in plane.lines:
            if line.name == trace_reduce.OPS_LINE or "python" in line.name:
                names = Counter(e.name for e in line.events)
                print(f"  line {line.name!r} top names {names.most_common(8)}")
    red = trace_reduce.reduce(args.out)
    print(json.dumps(red)[:4000])
    print(f"size_bytes {args.out.stat().st_size}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
