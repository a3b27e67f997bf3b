"""Puts the repository root on ``sys.path`` so the tests import the
benchmark package (``chipbench``) beside the program (``src/repro``)."""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
