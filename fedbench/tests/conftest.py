"""Tests of the benchmark harness, on the CPU at small sizes.

    python -m pytest fedbench/tests

They sit outside the repository's ``testpaths`` and import the harness's
modules and the program from the checkout this file is in.
"""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT / "fedbench"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
