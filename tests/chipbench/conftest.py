"""Put the repo root on the path, so that the tests import the benchmark
as the ``chipbench`` package (its ``trace`` module would shadow the
standard library's if its directory were on the path)."""
import pathlib
import sys

_ROOT = str(pathlib.Path(__file__).resolve().parents[2])
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)
