"""Tests of the benchmark harness: the harness's own modules import as
``harness``, the program as ``repro``, the tests' helpers by their names."""
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src", BENCH / "tests"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
