"""The benchmark's own tests: run on the CPU with
``JAX_PLATFORMS=cpu python -m pytest bench/tests`` (the control test
needs the chip: ``python -m pytest bench/tests/test_control.py``)."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
