"""Tests of the benchmark's own code, on the CPU:

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
