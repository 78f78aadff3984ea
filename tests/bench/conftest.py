"""Puts the benchmark's own directory (benchmarks/chip) and the program
(src) on the path for the benchmark's CPU tests."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
for p in (os.path.join(ROOT, "src"), os.path.join(BENCH, "metrics"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
