"""The benchmark's own tests: ``python3 -m pytest benchmarks/tests -q``
from the root of the checkout, on the CPU.  They live outside ``tests/``,
so the repo's tier-1 count neither gains nor loses by them."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
