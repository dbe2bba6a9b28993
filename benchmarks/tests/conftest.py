"""Tests of the benchmark itself: run on the CPU with

    pytest benchmarks/tests -q

Tier-1 collects them too since PR 31, through the link `tests/benchmark_harness`:
they count there, and one that costs more than ~30 s has to be marked `slow`.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
