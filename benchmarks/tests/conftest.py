"""benchmarks/tests are run by hand (`JAX_PLATFORMS=cpu python -m pytest
benchmarks/tests -q`), not by the tier-1 command."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (BENCH, os.path.join(BENCH, "lib")):
    if p not in sys.path:
        sys.path.insert(0, p)
