"""The yardstick's arithmetic against hand counts."""

import json
import os

import pytest

import harness
import work


def test_bcd_flops_hand_count():
    # One block of 4 columns, 8 rows, 2 classes, 1 epoch.
    n, b, k = 8, 4, 2
    once = 2 * n * b * b + b**3 / 3 + 2 * b**3
    epoch = 3 * (2 * n * b * k) + 2 * b * b * k
    assert work.bcd_flops(n, b, k, b, 1) == pytest.approx(once + epoch)
    # Three blocks, two epochs: every block pays once and twice the epoch.
    assert work.bcd_flops(n, 3 * b, k, b, 2) == pytest.approx(3 * (once + 2 * epoch))


def test_bcd_flops_is_bench_py_s():
    """The copy agrees with the original at the two cells' shapes."""
    import sys

    sys.path.insert(0, harness.ROOT)
    import bench

    for shape in (dict(n=8192, d=65536, k=1000, block=8192, iters=3),
                  dict(n=2048, d=262144, k=147, block=2048, iters=3)):
        assert work.bcd_flops(**shape) == bench.bcd_flops(**shape)


def test_bcd_bytes_hand_count():
    n, d, k, b, it = 8, 12, 2, 4, 2
    a = n * d * (1 + 3 * it)          # grams once, three passes an epoch
    inv = 3 * b * b * (1 + it)        # written once, read once an epoch
    w = 2 * d * k * it                # read and written per visit
    r = 2 * n * k * 3 * it            # read and written per visit
    assert work.bcd_bytes(n, d, k, b, it) == 4 * (a + inv + w + r)


def test_cells_canonical_work():
    """flops(sizes) of both configurations at their published sizes."""
    im = harness.load_cell("imagenet-fit")
    f = im["adapter"].flops(im["sizes"], work)
    # gram 8 x 2 x 8192^3, Cholesky and inverse 8 x (7/3) 8192^3, three epochs.
    b = 8192
    once = 8 * (2 * b**3 + b**3 / 3 + 2 * b**3)
    epochs = 3 * 8 * (3 * 2 * b * b * 1000 + 2 * b * b * 1000)
    assert f["solver"] == pytest.approx(once + epochs)
    assert 31e12 < f["solver"] < 33e12
    m = 169
    project = 2 * 8192 * m * 64 * (128 + 96)
    em = 2 * 20 * 4 * 2 * 200000 * 256 * 64
    encode = 2 * 4 * 2 * 8192 * m * 256 * 64
    assert f["featurize"] == pytest.approx(project + em + encode)


def test_roofline_names_its_bound():
    peaks = work.chip_peaks("TPU v5 lite")
    assert work.roofline_seconds(197e12, 1.0, peaks) == (pytest.approx(1.0), "compute")
    assert work.roofline_seconds(1.0, 819e9, peaks) == (pytest.approx(1.0), "memory")
    assert work.roofline_seconds(197e12, 1.0, peaks, chips=4)[0] == pytest.approx(0.25)


def test_unknown_chip_is_an_error():
    with pytest.raises(KeyError):
        work.chip_peaks("TPU v9 imaginary")


def test_benchmark_json_names_files_that_exist():
    bench = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    configs = {c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        doc = json.load(open(os.path.join(harness.ROOT, c["file"])))
        assert doc["name"] == c["name"] and set(c["reduced"]) == set(doc["reduced"])
        assert os.path.exists(os.path.join(harness.HERE, "configs", doc["adapter"]))
    for w in bench["workloads"]:
        assert w["config"] in configs and len(w["why"]) <= 200
        doc = json.load(open(os.path.join(harness.HERE, "workloads", w["name"] + ".json")))
        assert doc["config"] == w["config"] and doc["chips"] == w["chips"]
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        doc = json.load(open(os.path.join(harness.HERE, "metrics", m["name"] + ".json")))
        assert doc["layer"] == m["layer"] and doc["unit"] == m["unit"]
