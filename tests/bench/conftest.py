"""Put ``bench/`` on the path for the benchmark's tests (its modules
import one another by their bare names, as ``bench/run.py`` does)."""

import os
import sys

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

