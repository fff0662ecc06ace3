"""The trace read by the program's own names (``trace_program.py``, through
the wire-format reader ``xspace.py``), on two traces recorded on a TPU v5e
chip by ``bench/record_fixture.py``: ``trace_small`` from a program with
no spans, scopes or role names, ``trace_spans`` from one with them."""
import bench_paths  # noqa: F401  (the benchmark and src on the path)
import gzip
import json
import math
import shutil
import sys
from pathlib import Path

import pytest

import trace_program
import trace_reduce
import xspace

DATA = Path(__file__).resolve().parent / "data"


def _unzip(tmp_path_factory, name):
    path = tmp_path_factory.mktemp("trace") / name
    with gzip.open(DATA / f"{name}.gz", "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return path


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    return _unzip(tmp_path_factory, "trace_small.xplane.pb")


@pytest.fixture(scope="module")
def named(tmp_path_factory):
    return _unzip(tmp_path_factory, "trace_spans.xplane.pb")


@pytest.fixture(scope="module")
def named_red(named):
    return trace_program.reduce(named)


@pytest.mark.parametrize("which", ["small", "named"])
def test_shared_keys_equal_trace_reduce(which, request):
    path = request.getfixturevalue(which)
    old, new = trace_reduce.reduce(path), trace_program.reduce(path)
    for key in ("window_s", "busy_s", "chips", "spans"):
        assert new[key] == old[key], key
    assert [t for _, t in new["idle_gaps"]] == [t for _, t in old["idle_gaps"]]
    assert [t for _, t in new["device_ops"]] != []
    assert "tensorflow" not in sys.modules


def test_wire_reader_matches_profile_data(small):
    import jax

    data = jax.profiler.ProfileData.from_file(str(small))
    want = {p.name: [(e.name, e.start_ns, e.duration_ns)
                     for line in p.lines if line.name == "XLA Ops"
                     for e in line.events]
            for p in data.planes if p.name.startswith("/device:TPU:")}
    planes = xspace.read(small, lambda n: n.startswith("/device:TPU:"))
    assert [p.name for p in planes] == list(want) == ["/device:TPU:0"]
    (plane,) = planes
    (ops,) = [line for line in plane.lines if line.name == "XLA Ops"]
    got = [(plane.event_metadata[e.metadata_id].name,
            ops.timestamp_ns + e.offset_ps // 1000, e.duration_ps // 1000)
           for e in ops.events]
    assert got == want["/device:TPU:0"]

    tf_ops = {plane.event_metadata[e.metadata_id].stats.get("tf_op")
              for e in ops.events}
    assert "jit(fn)/while/body/bnhd,bmhd->bhnm/dot_general:" in tf_ops
    assert "jit(_threefry_seed)/concatenate:" in tf_ops
    meta = plane.event_metadata[ops.events[0].metadata_id].stats
    assert {"flops", "bytes_accessed", "tf_op"} <= set(meta)
    (mods,) = [line for line in plane.lines if line.name == "XLA Modules"]
    names = {trace_program.jit_name(plane.event_metadata[e.metadata_id].name)
             for e in mods.events}
    assert names == {"convert_element_type", "_threefry_seed",
                     "_threefry_fold_in", "_lambda", "fn"}


def test_scope_paths_and_jit_names():
    assert trace_program.scope_path(
        "jit(segment_small)/while/body/mmdit/jit(gelu)/tanh:") == [
            "mmdit", "tanh"]
    assert trace_program.scope_path(
        "jit(segment_large)/while/body/mmdit/attention/"
        "bnhd,bmhd->bhnm/dot_general:") == [
            "mmdit", "attention", "bnhd,bmhd->bhnm", "dot_general"]
    assert trace_program.scope_path("") == []
    assert trace_program.jit_name("jit_segment_large(123)") == \
        "segment_large"
    assert trace_program.jit_name("jit__threefry_seed(5)") == \
        "_threefry_seed"


def _hand_made():
    """Two traced batches of a relay with 3 large and 2 small steps."""
    spans = [{"name": "generate_bucketed", "batch": k, "seconds": 1.0,
              "busy_s": 0.99} for k in (0, 1)]
    spans.append({"name": "aggregator", "batch": None, "seconds": 0.1,
                  "busy_s": 0.0})
    program = []
    for k in (0, 1):
        program += [
            {"name": "executor.prepare", "stats": {}, "batch": k,
             "seconds": 0.004, "busy_s": 0.001},
            {"name": "executor.dispatch", "stats": {}, "batch": k,
             "seconds": 0.3, "busy_s": 0.3},
            {"name": "executor.segment", "stats": {"role": "large",
                                                   "steps": 3},
             "batch": k, "seconds": 0.1, "busy_s": 0.1},
            {"name": "executor.segment", "stats": {"role": "small",
                                                   "steps": 2},
             "batch": k, "seconds": 0.1, "busy_s": 0.1},
            {"name": "executor.fetch", "stats": {}, "batch": k,
             "seconds": 0.696, "busy_s": 0.694},
        ]
    return {"window_s": 2.1, "busy_s": 1.98, "chips": 1, "spans": spans,
            "device_ops": [], "idle_gaps": [], "program_spans": program,
            "modules": {"segment_large": {"device_s": 1.2, "executions": 2},
                        "segment_small": {"device_s": 0.6, "executions": 2},
                        "noise": {"device_s": 0.001, "executions": 2}},
            "scopes": {"mmdit/attention/bnhd,bmhd->bhnm": 0.3,
                       "mmdit/attention": 0.1, "mmdit/attn_out": 0.2,
                       "mmdit/mlp": 0.9, "": 0.01}}


def test_quantities_of_a_hand_made_reduction():
    q = trace_program.quantities(_hand_made())
    assert q == pytest.approx({
        "prepare_idle_ms_per_batch": 3.0,
        "fetch_idle_ms_per_batch": 2.0,
        "large_step_ms": 1.2e3 / 6,
        "small_step_ms": 0.6e3 / 4,
        "attention_ms_per_batch": 0.4e3 / 2,
    })


def test_quantities_need_the_program_names():
    red = _hand_made()
    for key in ("program_spans", "modules", "scopes"):
        del red[key]
    assert trace_program.quantities(red) == {}
    # per-batch quantities need traced batches; per-step ones do not
    red = _hand_made()
    red["spans"] = [s for s in red["spans"] if s["batch"] is None]
    assert trace_program.quantities(red) == {
        "large_step_ms": pytest.approx(200.0),
        "small_step_ms": pytest.approx(150.0)}


def test_parent_trace_has_no_program_names(small):
    red = trace_program.reduce(small)
    assert red["program_spans"] == []
    assert set(red["modules"]) >= {"fn"}
    assert trace_program.quantities(red) == {}


def test_program_spans_nest_in_the_harness_batches(named_red):
    red = named_red
    rows = red["program_spans"]
    names = {s["name"] for s in rows}
    assert {"executor.prepare", "executor.dispatch", "executor.fetch",
            "executor.segment"} <= names
    assert all(s["batch"] is not None for s in rows)
    batches = {s["batch"] for s in trace_program.traced_batches(red)}
    assert {s["batch"] for s in rows} == batches
    for s in rows:
        assert 0 <= s["busy_s"] <= s["seconds"] + 1e-9
    segs = [s for s in rows if s["name"] == "executor.segment"]
    assert {s["stats"]["role"] for s in segs} == {"large", "small"}
    assert all(s["stats"]["steps"] > 0 for s in segs)
    # every idle gap inside a batch is put down to a program span
    for owner, _ in red["idle_gaps"]:
        assert owner != "generate_bucketed"


def test_scopes_cover_the_segment_programs(named):
    devices, modules, _ = trace_program.read(named)
    inside = scoped = 0.0
    for d, ops in devices.items():
        execs = modules[d]
        starts = [x[1] for x in execs]
        for _, a, b, tf_op, _, _ in trace_reduce._leaves(ops):
            jit = trace_program._module_of(execs, starts, a)
            if jit.startswith("segment_"):
                inside += b - a
                parts = trace_program.scope_path(tf_op)
                scoped += (b - a) * (len(parts) > 1)
    assert inside > 0 and scoped >= 0.9 * inside


def test_named_trace_reads_every_quantity(named_red):
    red = named_red
    assert {"segment_large", "segment_small", "noise",
            "request_keys"} <= set(red["modules"])
    q = trace_program.quantities(red)
    assert set(q) == {"prepare_idle_ms_per_batch", "fetch_idle_ms_per_batch",
                      "large_step_ms", "small_step_ms",
                      "attention_ms_per_batch"}
    assert all(math.isfinite(v) and v >= 0 for v in q.values())
    assert q["attention_ms_per_batch"] > 0 and q["large_step_ms"] > 0
    kinds = [k for k, _ in red["device_ops"]]
    assert all(len(k) <= trace_reduce.OP_NAME_CHARS for k in kinds)
    assert any(k.startswith(("segment_large/mmdit/",
                             "segment_small/mmdit/")) for k in kinds)


def test_command_reads_a_gzipped_trace(capsys):
    assert trace_program.main([str(DATA / "trace_small.xplane.pb.gz")]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["quantities"] == {} and out["program_spans"] == []
    assert out["busy_s"] < out["window_s"]
