"""The benchmark's own tests run on the CPU at sizes a test run holds:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import repro  # noqa: E402,F401  (x64 before any array is made)
