"""benchmarks/tests run on the CPU at tiny size (the repo's tests/conftest.py
does not reach this directory). Not part of tier-1:
`JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider`."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
