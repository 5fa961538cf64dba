"""benchmarks/tests: run by hand and in rehearsal,

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

not part of tier-1 (`tests/`).
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
