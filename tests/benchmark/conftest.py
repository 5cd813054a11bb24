"""The benchmark's own modules (``harness``, ``flops``) import as they do
under ``benchmark/run.py``: with ``benchmark/`` on the path."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
