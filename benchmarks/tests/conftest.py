"""These tests live with the benchmark (``pytest benchmarks/tests``), not under
``tests/``: tier-1 collects what it collected before. CPU only; nothing here
describes a topology or touches a TPU at import time."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
