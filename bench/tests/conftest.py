"""Puts the benchmark's harness, the program and the test helpers on the path."""
import pathlib
import sys

_BENCH = pathlib.Path(__file__).resolve().parents[1]
for _p in (_BENCH / "tests", _BENCH.parent / "src", _BENCH):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))
