"""The benchmark's own tests: `python -m pytest benchmark/tests -q` under
an explicit JAX_PLATFORMS=cpu. They are not part of tier-1 (`tests/`)."""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for path in (BENCH, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)
