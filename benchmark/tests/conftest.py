"""The benchmark's own tests: run by hand, on the CPU, never by tier-1.

    python3 -m pytest benchmark/tests -q

JAX is held to the CPU before anything imports it."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
