"""Per-host shard ingest (SURVEY.md §7 hard part 4; VERDICT r2 #7).

Host h of H decodes only slice h of the sorted synset list. Validated
in-process (disjointness/union/labels) and across two REAL processes —
the 2-host ingest pattern as code, against the committed real-format
ImageNet fixture (one .tar synset + one directory synset).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from keystone_tpu.loaders.imagenet import ImageNetLoader, _pool_workers

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(os.path.dirname(__file__), "fixtures", "data", "imagenet")


def test_pool_workers_capped_at_core_count():
    cores = os.cpu_count() or 1
    assert _pool_workers(None) == min(16, cores)
    assert _pool_workers(64) == min(64, cores)
    assert _pool_workers(1) == 1


def test_shards_are_disjoint_and_cover():
    label_map = ImageNetLoader.load_label_map(os.path.join(DATA, "labels.txt"))
    root = os.path.join(DATA, "train")
    full = [
        (len(buf), label)
        for buf, label in ImageNetLoader.iter_jobs(root, label_map)
    ]
    for num_hosts in (2, 3):
        parts = [
            [
                (len(buf), label)
                for buf, label in ImageNetLoader.iter_jobs(
                    root, label_map, shard=(h, num_hosts)
                )
            ]
            for h in range(num_hosts)
        ]
        union = [job for part in parts for job in part]
        assert sorted(union) == sorted(full)  # cover, no duplicates
    with pytest.raises(ValueError, match="shard index"):
        list(ImageNetLoader.iter_jobs(root, label_map, shard=(2, 2)))


@pytest.mark.parametrize("rows, klass", [(128, "shard"), (125, "pad"), (24, "small")])
def test_placed_batch_follows_the_dataset_operators_rule_on_the_mesh(rows, klass):
    """On the fake mesh ``placed_batch`` puts a divisible host batch where
    ``DatasetOperator.execute`` would, and leaves one the chain has to
    mask-pad (or one under the row floor) on the host, untouched, for
    every walk's operator to defer as before."""
    import jax

    from keystone_tpu.utils.mesh import data_sharding, host_batch_shard_class
    from keystone_tpu.utils.metrics import sharding_counters
    from keystone_tpu.workflow import placed_batch
    from keystone_tpu.workflow.operators import DatasetOperator

    X = np.arange(rows * 6, dtype=np.float32).reshape(rows, 6)
    assert host_batch_shard_class(X) == klass
    want = DatasetOperator(X).execute([])
    before = sharding_counters.snapshot()
    with placed_batch(X) as placed:
        after = sharding_counters.snapshot()
        if klass != "shard":
            assert placed is X and want is X and after == before
            return
        assert isinstance(placed, jax.Array) and placed.sharding == data_sharding()
        assert placed.sharding == want.sharding
        assert after.get("batches_sharded") == before.get("batches_sharded", 0) + 1
        np.testing.assert_array_equal(np.asarray(placed), X)
        # Placed for every walk: an operator over it places nothing again
        # and signs as the host array's would.
        op = DatasetOperator(placed)
        assert op.execute([]) is placed
        assert sharding_counters.snapshot() == after
        assert op.signature() == DatasetOperator(X).signature()


_WORKER = r"""
import json, os, sys
sys.path.insert(0, {repo!r})
from keystone_tpu.loaders.imagenet import ImageNetLoader

h, H = int(sys.argv[1]), int(sys.argv[2])
label_map = ImageNetLoader.load_label_map(os.path.join({data!r}, "labels.txt"))
batches = list(ImageNetLoader.stream_batches(
    os.path.join({data!r}, "train"), label_map,
    batch_size=2, size=16, workers=1, shard=(h, H),
))
out = {{
    "host": h,
    "labels": [int(l) for _X, y in batches for l in y],
    "pixels": [round(float(X.mean()), 4) for X, _y in batches],
}}
print(json.dumps(out))
"""


@pytest.mark.slow
def test_two_process_sharded_ingest():
    """Two real processes each stream their shard; together they cover the
    dataset exactly once — the multi-host ingest seam as running code."""
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _WORKER.format(repo=REPO, data=DATA), str(h), "2"],
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        for h in range(2)
    ]
    outs = []
    for p in procs:
        stdout, stderr = p.communicate(timeout=120)
        assert p.returncode == 0, stderr[-2000:]
        outs.append(json.loads(stdout.strip().splitlines()[-1]))

    label_map = ImageNetLoader.load_label_map(os.path.join(DATA, "labels.txt"))
    full_labels = sorted(
        label
        for _buf, label in ImageNetLoader.iter_jobs(
            os.path.join(DATA, "train"), label_map
        )
    )
    got = sorted(l for o in outs for l in o["labels"])
    assert got == full_labels  # disjoint cover across the two processes
    # Each host actually decoded pixels (not just listed files).
    assert all(len(o["pixels"]) >= 1 for o in outs)
