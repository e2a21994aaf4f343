"""The benchmark's own tests: `JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`.

They drive the harness through its functions at tiny widths on the CPU
(counts and `correct` only: `run.py` itself still refuses a CPU)."""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

with open(os.path.join(HERE, "data", "tiny.json")) as f:
    _tiny = json.load(f)
TINY, TINY_LIMITS = _tiny["sizes"], _tiny["limits"]
