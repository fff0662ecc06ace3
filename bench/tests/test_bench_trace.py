"""The trace reduction, on a small trace recorded on a TPU v5e chip by
``bench/record_fixture.py`` (the tiny configuration, 0.5 s of open-loop
traffic, ``--trace 1``) and checked in gzipped."""
import bench_paths  # noqa: F401  (the benchmark and src on the path)
import gzip
import shutil
from pathlib import Path

import pytest

import trace_reduce

FIXTURE = Path(__file__).resolve().parent / "data" / \
    "trace_small.xplane.pb.gz"


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "trace_small.xplane.pb"
    with gzip.open(FIXTURE, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return trace_reduce.reduce(path)


def test_window_and_busy(reduced):
    assert reduced["chips"] == 1
    assert 0 < reduced["busy_s"] < reduced["window_s"]


def test_spans_are_named_and_batched(reduced):
    names = {s["name"] for s in reduced["spans"]}
    assert {"generate_bucketed", "aggregator"} <= names
    batches = [s["batch"] for s in reduced["spans"]
               if s["name"] == "generate_bucketed"]
    assert batches == list(range(batches[0], batches[0] + len(batches)))
    for s in reduced["spans"]:
        assert 0 <= s["busy_s"] <= s["seconds"] + 1e-9
    # the device works inside the program's calls, not in the harness's
    inside = sum(s["busy_s"] for s in reduced["spans"]
                 if s["name"] == "generate_bucketed")
    assert inside > 0.9 * reduced["busy_s"]


def test_breakdown_lists(reduced):
    ops, gaps = reduced["device_ops"], reduced["idle_gaps"]
    assert 0 < len(ops) <= 10 and 0 < len(gaps) <= 10
    assert [t for _, t in ops] == sorted((t for _, t in ops), reverse=True)
    assert [t for _, t in gaps] == sorted((t for _, t in gaps), reverse=True)
    assert 0 < ops[0][1] <= reduced["busy_s"]
    # leaf operations do not overlap, so their times add up within busy
    assert sum(t for _, t in ops) <= reduced["busy_s"] + 1e-9
    assert all(len(name) <= trace_reduce.OP_NAME_CHARS for name, _ in ops)
    assert all(t > 0 for _, t in gaps)
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(t for _, t in gaps) <= idle + 1e-9


def test_op_kinds_and_leaves():
    a = "%fusion.1600 = f32[8]{0} fusion(f32[8]{0} %get-tuple-element.35)"
    b = "%fusion.1616 = f32[8]{0} fusion(f32[8]{0} %get-tuple-element.40)"
    assert trace_reduce.op_kind(a) == trace_reduce.op_kind(b) == \
        "%fusion = f32[8]{0} fusion(f32[8]{0} %get-tuple-element)"
    assert trace_reduce.op_kind("f32[1,32,32,16]") == "f32[1,32,32,16]"
    ops = [("%while.9", 0, 10), ("%fusion.1", 1, 4), ("%fusion.2", 4, 9),
           ("%copy.3", 12, 13)]
    assert [o[0] for o in trace_reduce._leaves(ops)] == [
        "%fusion.1", "%fusion.2", "%copy.3"]


def test_merge_and_cover():
    m = trace_reduce._merge([(0, 1), (0.5, 2), (3, 4), (5, 6)])
    assert m == [(0, 2), (3, 4), (5, 6)]
    assert trace_reduce._covered(m, 0.5, 3.5) == pytest.approx(2.0)
    assert trace_reduce._covered(m, -1, 10) == pytest.approx(4.0)
    assert trace_reduce._covered(m, 4, 5) == 0.0
