"""Run by hand: ``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``
from the root of the checkout. Tier-1 does not collect this directory."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for path in (ROOT, BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)
