"""Puts the repository root (for ``perfbench``) and ``src`` (for the
program under test) on the import path of the benchmark's tests."""
import pathlib
import sys

_ROOT = pathlib.Path(__file__).resolve().parent.parent
for _p in (str(_ROOT), str(_ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)
