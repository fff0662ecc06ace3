"""The harness finds every cell, configuration, traffic mix and metric from
its files by name, ``BENCHMARK.json`` keeps to the benchmark's contract, and
the command refuses to run off a TPU."""
import bench_paths  # noqa: F401  (the benchmark and src on the path)
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import harness

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"][1].startswith(SPEC["paths"][0] + "/")
    assert 1 <= SPEC["run_seconds"] <= 51


def test_entries_keep_to_the_contract():
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves"}}
    for group, want in keys.items():
        names = [e["name"] for e in SPEC[group]]
        assert len(names) == len(set(names))
        for e in SPEC[group]:
            assert set(e) - {"workloads"} == want, (group, e["name"])
            assert NAME.match(e["name"])
            if "unit" in e:
                assert UNIT.match(e["unit"]) and e["better"] in ("lower",
                                                                 "higher")
    texts = ([e["why"] for g in ("configs", "workloads") for e in SPEC[g]]
             + [c["source"] for c in SPEC["configs"]]
             + [m["layer"] for m in SPEC["per_layer"]] + SPEC["command"])
    for text in texts:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    for c in SPEC["configs"]:
        assert len(c["reduced"]) <= 16 and all(NAME.match(k)
                                               for k in c["reduced"])
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        for cell in m["workloads"]:
            assert cell in e2e[m["moves"]].get("workloads", CELLS)


@pytest.mark.parametrize("name", CELLS)
def test_cell_found_from_its_files(name):
    cell = harness.load_cell(name, ROOT)
    assert cell.chips == 1
    arch = cell.config["architecture"]
    assert (BENCH / "configs" / f"{arch}.py").is_file()
    assert (BENCH / "configs" / f"{arch}_ref.py").is_file()
    assert set(cell.traffic["buckets"]) <= set(cell.config["buckets"])
    labels = {a["label"] for a in cell.config["arms"]}
    assert set(cell.traffic["arms"]) <= labels
    e2e = [m["name"] for m in cell.end_to_end]
    assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert harness.metric_reader(m["name"]).is_file(), m["name"]


def test_every_config_file_states_its_cut():
    for entry in SPEC["configs"]:
        cfg = json.loads((ROOT / entry["file"]).read_text())
        assert entry["file"].startswith(SPEC["paths"][0] + "/")
        assert cfg["name"] == entry["name"]
        for key in ("departures", "deployment", "precision", "published",
                    "check"):
            assert cfg[key], key
        published = cfg["published"]["large"]
        changed = {k for k, v in cfg.items()
                   if k in published and published[k] != v}
        if cfg["medium"] != {k: cfg["published"]["medium"].get(k)
                             for k in cfg["medium"]}:
            changed.add("medium")
        assert changed == set(entry["reduced"])


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_exits_nonzero_off_a_tpu():
    r = _run(ROOT)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert not r.stdout.strip()


def test_command_exits_nonzero_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0
    assert not r.stdout.strip()
