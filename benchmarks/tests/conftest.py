"""Tests of the benchmark itself: run on the CPU with

    pytest benchmarks/tests -q

Tier-1 collects `tests/` only, so these neither add to nor take from its count.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
