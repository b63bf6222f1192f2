"""The harness's own tests (not tier-1): run them on the CPU with

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
