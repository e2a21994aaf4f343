"""Record the small trace that `tests/test_tracereduce.py` reduces: one
traced run of a cell at the tiny widths of `tests/data/tiny.json`, on the
chip, through the harness's own path.

    python3 benchmark/tools/record_trace.py imagenet-fit chiprun_out/tiny.xplane.pb
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import harness

cell, out = sys.argv[1], sys.argv[2]
with open(os.path.join(os.path.dirname(HERE), "tests", "data", "tiny.json")) as f:
    tiny = json.load(f)
os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
result = harness.run_cell(
    cell, 2200000007, 0.0, True, keep_trace=out,
    overrides={"sizes": tiny["sizes"][cell], "limits": tiny["limits"]})
print(json.dumps(result))
