"""Reduction of a JAX profiler trace to what the per-layer metrics read.

A run with ``--trace 1`` wraps its traced part in a host span
``bench:window`` and each call into the program in a span of its own
(``bench:generate_bucketed`` with the batch number, ``bench:aggregator``,
``bench:arrival_wait``). The device planes (``/device:TPU:<n>``) hold one
event per XLA operation on the line ``XLA Ops``. This module puts both on
the profiler's clock and returns, for the traced window: the device busy
seconds (the union of operation intervals, averaged over the chips), the
busy seconds inside each host span, the kinds of operation that took most
time (operations that enclose others, such as a ``while``, are left out, so
the times do not overlap), and the longest idle gaps by the host span they
fall in.
"""
from __future__ import annotations

import bisect
import math
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "bench:"
WINDOW_SPAN = SPAN_PREFIX + "window"
OPS_LINE = "XLA Ops"


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _covered(merged: List[Tuple[float, float]], a: float, b: float) -> float:
    """Length of [a, b] covered by the merged (sorted, disjoint) intervals."""
    total = 0.0
    k = bisect.bisect_right(merged, (a, math.inf)) - 1
    for x, y in merged[max(k, 0):]:
        if x >= b:
            break
        total += max(0.0, min(b, y) - max(a, x))
    return total


_INSTANCE = re.compile(r"(%[A-Za-z0-9_-]+)\.\d+")
OP_NAME_CHARS = 200


def op_kind(name: str) -> str:
    """An operation's HLO text with instance numbers dropped
    (``%fusion.1600`` -> ``%fusion``), cut to ``OP_NAME_CHARS``: the same
    operation in every block of a model, at the same shapes, is one kind."""
    return _INSTANCE.sub(r"\1", name)[:OP_NAME_CHARS]


def _leaves(ops):
    """The operations that enclose no other: a ``while`` or a call spans
    the operations of its body on the same line."""
    ops = sorted(ops, key=lambda o: o[1])
    return [o for o, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt[1] >= o[2]]


def read(path: Path):
    """(device op events per chip, host spans) from an ``.xplane.pb``.
    Device events are (name, start_ns, end_ns); host spans are
    (name, start_ns, end_ns, stats)."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    devices: Dict[str, list] = {}
    spans = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns,
                                      dict(e.stats)))
    return devices, spans


def reduce(path: Path, top: int = 10) -> Optional[dict]:
    """The traced window's device busy time and its attribution; None
    where the trace holds no TPU operation or no window span."""
    devices, spans = read(path)
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    if not devices or not windows:
        return None
    _, w0, w1, _ = windows[0]
    inner = sorted((s for s in spans if s[0] != WINDOW_SPAN
                    and s[1] >= w0 and s[2] <= w1), key=lambda s: s[1])
    merged = {d: _merge([(a, b) for _, a, b in ops if b > w0 and a < w1])
              for d, ops in devices.items()}
    n = len(merged)
    busy_ns = sum(_covered(m, w0, w1) for m in merged.values()) / n

    span_rows = []
    for name, a, b, stats in inner:
        busy = sum(_covered(m, a, b) for m in merged.values()) / n
        span_rows.append({"name": name[len(SPAN_PREFIX):],
                          "batch": stats.get("batch"),
                          "seconds": (b - a) * 1e-9,
                          "busy_s": busy * 1e-9})

    op_time: Dict[str, float] = defaultdict(float)
    for ops in devices.values():
        for name, a, b in _leaves(ops):
            op_time[op_kind(name)] += (max(0.0, min(b, w1) - max(a, w0))
                                       * 1e-9 / n)
    device_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]

    # the longest idle gaps of the first chip, named by the host span
    # around their middle
    first = merged[sorted(merged)[0]]
    edges = [w0] + [x for iv in first for x in iv] + [w1]
    gaps = sorted(((b - a, a, b) for a, b in zip(edges[0::2], edges[1::2])
                   if b > a), reverse=True)[:top]
    idle_gaps = []
    for length, a, b in gaps:
        mid = (a + b) / 2
        owner = next((s[0][len(SPAN_PREFIX):] for s in inner
                      if s[1] <= mid <= s[2]), "between_spans")
        idle_gaps.append([owner, length * 1e-9])

    return {"window_s": (w1 - w0) * 1e-9, "busy_s": busy_ns * 1e-9,
            "chips": n, "spans": span_rows,
            "device_ops": [[k, v] for k, v in device_ops],
            "idle_gaps": idle_gaps}
