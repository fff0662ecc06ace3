#!/usr/bin/env python3
"""The traced window by the program's own names: its host spans, the jit
name of each device program and the named scope of each operation.

``trace_reduce.reduce`` reads the trace through ``jax.profiler.ProfileData``,
which holds no operation's metadata, and keeps only the harness's spans.
:func:`reduce` reads the same ``.xplane.pb`` with ``xspace.py`` and returns
every key of ``trace_reduce.reduce``, computed the same way (``window_s``,
``busy_s``, ``chips``, ``spans``), with ``device_ops`` and ``idle_gaps``
named by the program, and three more:

- ``program_spans``: each span of the program (``executor.*``) in the
  window, with its stats, busy seconds and the batch number of the
  ``bench:generate_bucketed`` span around it;
- ``modules``: device time (union of operation intervals inside its
  executions) and executions per jit name, ``jit_`` dropped;
- ``scopes``: leaf-operation device time per scope path, the operation's
  ``op_name`` with the jit name, ``while/body`` and nested ``jit(...)``
  dropped (``mmdit/attention/bnhd,bmhd->bhnm``).

The functions below it compute five per-layer quantities from a reduction.
Run it on a trace, or record one from a cell:

    python3 bench/trace_program.py trace.xplane.pb[.gz]
    python3 bench/trace_program.py --workload <cell> --seed <n> \\
        --seconds <s> --out <trace.xplane.pb>
"""
from __future__ import annotations

import argparse
import bisect
import gzip
import json
import re
import shutil
import sys
import tempfile
import time
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional

BENCH = Path(__file__).resolve().parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import trace_reduce as tr  # noqa: E402
import xspace  # noqa: E402

PROGRAM_PREFIX = "executor."
BATCH_SPAN = tr.SPAN_PREFIX + "generate_bucketed"
MODULES_LINE = "XLA Modules"
_LAYOUT = re.compile(r"\{[^{}]*\}")
_INSTANCE = re.compile(r"\.\d+$")


def jit_name(module: str) -> str:
    """``jit_segment_large(1234)`` -> ``segment_large``."""
    name = module.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def scope_path(tf_op: str) -> List[str]:
    """The named parts of an ``op_name`` path, the primitive last:
    ``jit(fn)/while/body/mmdit/jit(gelu)/tanh:`` -> ``[mmdit, tanh]``."""
    parts = tf_op.rsplit(":", 1)[0].split("/") if tf_op else []
    out: List[str] = []
    for k, p in enumerate(parts):
        if p.startswith("jit(") or (p == "while" and k + 1 < len(parts)
                                     and parts[k + 1] in ("body", "cond")):
            continue
        if p in ("body", "cond") and k and parts[k - 1] == "while":
            continue
        out.append(p)
    return out


def _ns(line, e):
    # whole nanoseconds, as jax.profiler.ProfileData gives them
    start = line.timestamp_ns + e.offset_ps // 1000
    return start, start + e.duration_ps // 1000


def read(path: Path):
    """(operations per chip, module executions per chip, host spans).
    An operation is (HLO text, start_ns, end_ns, op_name path, HLO op,
    shape); an execution (jit name, start_ns, end_ns); a span (name,
    start_ns, end_ns, stats), the harness's and the program's."""
    planes = xspace.read(path, lambda n: n.startswith("/device:TPU:")
                         or n.startswith("/host:"))
    ops, modules, spans = {}, {}, []
    for p in planes:
        meta = p.event_metadata
        if p.name.startswith("/device:TPU:"):
            for line in p.lines:
                if line.name == tr.OPS_LINE and line.events:
                    rows = []
                    for e in line.events:
                        m = meta[e.metadata_id]
                        shape = str(m.stats.get("shape_with_layout", ""))
                        while _LAYOUT.search(shape):
                            shape = _LAYOUT.sub("", shape)
                        rows.append((m.name, *_ns(line, e),
                                     str(m.stats.get("tf_op", "")),
                                     _INSTANCE.sub("", m.display_name),
                                     shape))
                    ops[p.name] = rows
                elif line.name == MODULES_LINE:
                    modules[p.name] = sorted(
                        ((jit_name(meta[e.metadata_id].name), *_ns(line, e))
                         for e in line.events), key=lambda r: r[1])
        else:
            for line in p.lines:
                for e in line.events:
                    name = meta[e.metadata_id].name
                    if name.startswith((tr.SPAN_PREFIX, PROGRAM_PREFIX)):
                        spans.append((name, *_ns(line, e), dict(e.stats)))
    return ops, {d: modules.get(d, []) for d in ops}, spans


def _module_of(execs, starts, t):
    k = bisect.bisect_right(starts, t) - 1
    if k >= 0 and execs[k][1] <= t <= execs[k][2]:
        return execs[k][0]
    return ""


def reduce(path: Path, top: int = 10) -> Optional[dict]:
    """``trace_reduce.reduce``'s keys and the program's names; None where
    the trace holds no TPU operation or no window span."""
    devices, modules, spans = read(path)
    windows = [s for s in spans if s[0] == tr.WINDOW_SPAN]
    if not devices or not windows:
        return None
    _, w0, w1, _ = windows[0]
    inner = sorted((s for s in spans if s[0] != tr.WINDOW_SPAN
                    and s[1] >= w0 and s[2] <= w1), key=lambda s: s[1])
    bench = [s for s in inner if s[0].startswith(tr.SPAN_PREFIX)]
    program = [s for s in inner if s[0].startswith(PROGRAM_PREFIX)]
    merged = {d: tr._merge([(o[1], o[2]) for o in ops
                            if o[2] > w0 and o[1] < w1])
              for d, ops in devices.items()}
    n = len(merged)

    def busy(a, b):
        return sum(tr._covered(m, a, b) for m in merged.values()) / n

    busy_ns = busy(w0, w1)
    span_rows = [{"name": name[len(tr.SPAN_PREFIX):],
                  "batch": stats.get("batch"),
                  "seconds": (b - a) * 1e-9, "busy_s": busy(a, b) * 1e-9}
                 for name, a, b, stats in bench]
    batches = [(s[1], s[2], s[3].get("batch")) for s in bench
               if s[0] == BATCH_SPAN]
    program_rows = []
    for name, a, b, stats in program:
        batch = next((k for x, y, k in batches if x <= a and b <= y), None)
        program_rows.append({"name": name, "stats": stats, "batch": batch,
                             "seconds": (b - a) * 1e-9,
                             "busy_s": busy(a, b) * 1e-9})

    mod_rows: Dict[str, dict] = defaultdict(
        lambda: {"device_s": 0.0, "executions": 0})
    for d, execs in modules.items():
        for jit, a, b in execs:
            if b > w0 and a < w1:
                row = mod_rows[jit]
                row["device_s"] += tr._covered(merged[d], max(a, w0),
                                               min(b, w1)) * 1e-9 / n
                row["executions"] += 1

    scopes: Dict[str, float] = defaultdict(float)
    op_time: Dict[str, float] = defaultdict(float)
    for d, ops in devices.items():
        execs = modules[d]
        starts = [x[1] for x in execs]
        for _, a, b, tf_op, hlo, shape in tr._leaves(ops):
            t = max(0.0, min(b, w1) - max(a, w0)) * 1e-9 / n
            if t <= 0:
                continue
            parts = scope_path(tf_op)
            scopes["/".join(parts[:-1])] += t
            jit = _module_of(execs, starts, a)
            kind = "/".join([jit] + parts) + f" {hlo} {shape}"
            op_time[kind[:tr.OP_NAME_CHARS]] += t
    device_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]

    # the longest idle gaps of the first chip, named by the innermost span
    # (the harness's or the program's) around their middle
    first = merged[sorted(merged)[0]]
    edges = [w0] + [x for iv in first for x in iv] + [w1]
    gaps = sorted(((b - a, a, b) for a, b in zip(edges[0::2], edges[1::2])
                   if b > a), reverse=True)[:top]
    idle_gaps = []
    for length, a, b in gaps:
        mid = (a + b) / 2
        around = [s for s in inner if s[1] <= mid <= s[2]]
        owner = "between_spans"
        if around:
            name = max(around, key=lambda s: (s[1], -s[2]))[0]
            owner = (name[len(tr.SPAN_PREFIX):]
                     if name.startswith(tr.SPAN_PREFIX) else name)
        idle_gaps.append([owner, length * 1e-9])

    return {"window_s": (w1 - w0) * 1e-9, "busy_s": busy_ns * 1e-9,
            "chips": n, "spans": span_rows,
            "device_ops": [[k, v] for k, v in device_ops],
            "idle_gaps": idle_gaps, "program_spans": program_rows,
            "modules": dict(mod_rows), "scopes": dict(scopes)}


# -- per-layer quantities of a reduction -------------------------------------


def traced_batches(red: dict) -> list:
    return [s for s in red["spans"]
            if s["name"] == "generate_bucketed" and s["batch"] is not None]


def idle_ms_per_batch(red: dict, span: str) -> Optional[float]:
    """Device idle inside the program span ``span`` (``executor.prepare``,
    ``executor.fetch``), per traced batch, in ms."""
    n = len(traced_batches(red))
    rows = [s for s in red.get("program_spans", [])
            if s["name"] == span and s["batch"] is not None]
    if not n or not rows:
        return None
    return 1e3 * sum(s["seconds"] - s["busy_s"] for s in rows) / n


def step_ms(red: dict, role: str) -> Optional[float]:
    """Device time of the ``segment_<role>`` executions per denoising step
    of that role, in ms; the steps are the ``executor.segment`` spans'
    host-known ``steps``."""
    steps = sum(int(s["stats"].get("steps", 0))
                for s in red.get("program_spans", [])
                if s["name"] == PROGRAM_PREFIX + "segment"
                and s["stats"].get("role") == role
                and s["batch"] is not None)
    mod = red.get("modules", {}).get(f"segment_{role}")
    if not steps or not mod:
        return None
    return 1e3 * mod["device_s"] / steps


def scope_ms_per_batch(red: dict, scope: str) -> Optional[float]:
    """Leaf device time under every scope path holding the part
    ``scope``, per traced batch, in ms."""
    n = len(traced_batches(red))
    times = [t for path, t in red.get("scopes", {}).items()
             if scope in path.split("/")]
    if not n or not times:
        return None
    return 1e3 * sum(times) / n


def quantities(red: dict) -> dict:
    """The five per-layer quantities that read the program's names."""
    out = {
        "prepare_idle_ms_per_batch": idle_ms_per_batch(
            red, PROGRAM_PREFIX + "prepare"),
        "fetch_idle_ms_per_batch": idle_ms_per_batch(
            red, PROGRAM_PREFIX + "fetch"),
        "large_step_ms": step_ms(red, "large"),
        "small_step_ms": step_ms(red, "small"),
        "attention_ms_per_batch": scope_ms_per_batch(red, "attention"),
    }
    return {k: v for k, v in out.items() if v is not None}


def _unzipped(path: Path, tmp: Path) -> Path:
    if path.suffix != ".gz":
        return path
    out = tmp / path.stem
    with gzip.open(path, "rb") as src, open(out, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", nargs="?", type=Path)
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=14.0)
    ap.add_argument("--out", type=Path)
    args = ap.parse_args(argv)
    if args.workload:
        import harness

        harness.pin_compile_cache()
        cell = harness.load_cell(args.workload, BENCH.parent)
        args.out.parent.mkdir(parents=True, exist_ok=True)
        res = harness.run_cell(cell, args.seed, args.seconds, True,
                               t_start=time.perf_counter(),
                               keep_trace=args.out)
        print(json.dumps(res), flush=True)
        args.trace = args.out
    with tempfile.TemporaryDirectory() as tmp:
        red = reduce(_unzipped(args.trace, Path(tmp)))
    if red is None:
        print("no TPU operation or no window span in the trace",
              file=sys.stderr)
        return 1
    print(json.dumps({"quantities": quantities(red),
                      **{k: red[k] for k in ("window_s", "busy_s",
                                             "device_ops", "idle_gaps",
                                             "modules")},
                      "scopes": dict(sorted(red["scopes"].items(),
                                            key=lambda kv: -kv[1])[:40]),
                      "program_spans": red["program_spans"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
