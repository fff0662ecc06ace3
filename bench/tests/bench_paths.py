"""Puts the benchmark's directory and the program's ``src`` on the import
path for the benchmark's own tests, which run on the CPU at tiny sizes.

A module of its own, not a ``conftest.py``: the repository's ``tests/``
imports names from its ``conftest`` module, and a second module of that
name would shadow it in the test workers that collect both.
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
