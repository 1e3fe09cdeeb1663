"""Tests of the benchmark's own arithmetic and control flow. Nothing here
needs a chip or describes one at import time; run with

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
