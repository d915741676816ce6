"""The benchmark's own tests run on the CPU, at sizes a CPU can hold:

    JAX_PLATFORMS=cpu python -m pytest bench/tests
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]
