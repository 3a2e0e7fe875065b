"""Tests of the chip benchmark run on the CPU: the harness, the reference
and the reductions, at reduced widths.  Run them with

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# programs compiled on the CPU are not worth keeping across runs
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parents[1] / "src")]
