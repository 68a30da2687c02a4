"""Tests of the benchmark itself.  Run by hand on the CPU, outside tier-1:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q -p no:cacheprovider
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (os.path.dirname(BENCH), BENCH, os.path.join(BENCH, "drivers")):
    if p not in sys.path:
        sys.path.insert(0, p)
