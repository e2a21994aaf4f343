"""Cross-process fitted-prefix reuse: content-stable signatures, structural
digests, the on-disk fit store, and NodeOptimizationRule memoization.

Ref: the reference's prefix-state reuse across fits (SURVEY.md §2.1
auto-caching row, §5 checkpoint/resume row) [unverified].
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from keystone_tpu.nodes.learning import LeastSquaresEstimator
from keystone_tpu.nodes.stats import StandardScaler
from keystone_tpu.workflow import LabelEstimator, PipelineEnv, Transformer
from keystone_tpu.workflow.fingerprint import (
    UNSTABLE,
    digest_tree,
    is_stable,
    stable_value,
)
from keystone_tpu.workflow.graph import structural_digest


def _data(seed=0, n=256, d=16, k=3):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    Y = rng.normal(size=(n, k)).astype(np.float32)
    return X, Y


class _MatTransformer(Transformer):
    jittable = False

    def __init__(self, W):
        self.W = W

    def apply_batch(self, B):
        return np.asarray(B) @ self.W


class CountingEstimator(LabelEstimator):
    """Closed-form ridge whose fits are counted — the cache-hit oracle."""

    fits = 0

    def __init__(self, lam: float = 1e-3):
        self.lam = lam

    def fit(self, data, labels):
        type(self).fits += 1
        X = np.asarray(data)
        Y = np.asarray(labels)
        W = np.linalg.solve(
            X.T @ X + self.lam * np.eye(X.shape[1]), X.T @ Y
        ).astype(np.float32)
        return _MatTransformer(W)


class TestStableSignatures:
    def test_identical_estimators_share_signature(self):
        a = LeastSquaresEstimator(lam=0.5, block_size=128)
        b = LeastSquaresEstimator(lam=0.5, block_size=128)
        assert a.signature() == b.signature()
        assert is_stable(stable_value(a.signature()))

    def test_hyperparams_distinguish(self):
        a = LeastSquaresEstimator(lam=0.5)
        b = LeastSquaresEstimator(lam=0.25)
        assert a.signature() != b.signature()

    def test_fit_time_diagnostics_do_not_change_signature(self):
        est = LeastSquaresEstimator(lam=0.5)
        before = est.signature()
        X, Y = _data()
        est.fit(X, Y)
        assert est.last_choice is not None  # the excluded mutable field moved
        assert est.signature() == before

    def test_unknown_objects_poison_but_stay_unique(self):
        o1, o2 = object(), object()  # keep both alive: distinct ids
        tree_a = stable_value({"fn": o1})
        tree_b = stable_value({"fn": o2})
        assert not is_stable(tree_a)
        assert tree_a != tree_b  # id keeps in-process uniqueness
        assert digest_tree(tree_a) is None

    def test_array_content_addresses(self):
        x = np.arange(12, dtype=np.float32).reshape(3, 4)
        y = x.copy()
        assert stable_value(x) == stable_value(y)
        z = y.copy()
        z[0, 0] += 1
        assert stable_value(x) != stable_value(z)

    def test_sampled_fingerprint_bounded_and_probing(self, monkeypatch):
        """Over-limit arrays with FEW, HUGE rows (n0 < 64) used to degrade to
        a full-buffer hash; now the per-chunk cap bounds pass 1 and the
        prime-strided element probe still sees changes past the cap."""
        from keystone_tpu.config import config
        from keystone_tpu.workflow.fingerprint import array_fingerprint

        monkeypatch.setattr(config, "fingerprint_max_bytes", 1 << 20)
        # 4 rows x 2 MiB: rows_per=1, so pass 1 hashes only the first 1 MiB
        # of each row. A change in the second MiB must still flip the digest
        # via the whole-array probe lattice (~32-element step here).
        a = np.zeros((4, 512 * 1024), dtype=np.float32)
        tag, shape, dt, dig = array_fingerprint(a)
        assert tag == "ndarray-sampled"
        b = a.copy()
        b[0, 300 * 1024 : 300 * 1024 + 512] = 1.0  # byte offset ~1.2 MiB
        assert array_fingerprint(b)[3] != dig
        assert array_fingerprint(a.copy())[3] == dig  # deterministic

    @pytest.mark.parametrize("shape, order", [
        ((64, 2048), (1, 0)),  # F-contiguous
        # Rows the minor axis of an image batch: what ``np.asarray`` of a
        # TPU array hands back, and what the sampled pass gathers in the
        # source's own memory order first.
        ((512, 8, 8, 3), (1, 3, 2, 0)),
    ], ids=["fortran", "rows_minor"])
    def test_sampled_fingerprint_layout_independent(self, monkeypatch, shape, order):
        """The same logical array, C-contiguous or laid out along other
        axes, must digest equal — the cross-process cache key can't depend
        on who materialized it."""
        from keystone_tpu.config import config
        from keystone_tpu.workflow.fingerprint import array_fingerprint

        monkeypatch.setattr(config, "fingerprint_max_bytes", 1 << 16)
        rng = np.random.default_rng(3)
        c = np.ascontiguousarray(rng.normal(size=shape).astype(np.float32))
        # ``order``: the axes from the slowest-varying in memory to the fastest.
        f = np.ascontiguousarray(c.transpose(order)).transpose(np.argsort(order))
        assert f.shape == c.shape and not f.flags.c_contiguous
        assert f.strides[order[-1]] == f.itemsize
        np.testing.assert_array_equal(f, c)
        assert array_fingerprint(c)[0] == "ndarray-sampled"
        assert array_fingerprint(c) == array_fingerprint(f)

    def test_sampled_fingerprint_noncontiguous_probed(self, monkeypatch):
        """Non-contiguous over-limit views get the element probe too: a
        change past pass 1's per-chunk cap still flips the digest."""
        from keystone_tpu.config import config
        from keystone_tpu.workflow.fingerprint import array_fingerprint

        monkeypatch.setattr(config, "fingerprint_max_bytes", 1 << 20)
        base = np.zeros((4, 1024 * 1024), dtype=np.float32)
        a = base[:, ::2]  # non-contiguous, 4 rows x 2 MiB
        dig = array_fingerprint(a)[3]
        base2 = base.copy()
        base2[0, 600 * 1024 : 600 * 1024 + 1024] = 1.0  # past the 1 MiB cap
        assert array_fingerprint(base2[:, ::2])[3] != dig


class TestStructuralDigest:
    def test_digest_stable_across_rebuilds(self):
        X, Y = _data()

        def build():
            p = StandardScaler().with_data(X.copy()).and_then(
                LeastSquaresEstimator(lam=1e-3), X.copy(), Y.copy()
            )
            return p

        from keystone_tpu.workflow.operators import EstimatorOperator

        digests = []
        for _ in range(2):
            p = build()
            g = p.graph
            for nid in g.reachable([p.sink]):
                if isinstance(g.operators[nid], EstimatorOperator):
                    digests.append(structural_digest(g, nid))
        assert digests and all(d is not None for d in digests)
        # Both estimator nodes (scaler + solver) match across rebuilds.
        assert digests[: len(digests) // 2] == digests[len(digests) // 2 :]

    def test_non_array_data_disables_digest(self):
        est = CountingEstimator()
        _, Y = _data(n=3)
        p = est.with_data([b"not", b"an", b"array"], Y)
        from keystone_tpu.workflow.operators import EstimatorOperator

        g = p.graph
        (enid,) = [
            nid
            for nid in g.reachable([p.sink])
            if isinstance(g.operators[nid], EstimatorOperator)
        ]
        assert structural_digest(g, enid) is None


class TestSessionCacheCrossInstance:
    def test_identical_pipelines_fit_once(self):
        X, Y = _data()
        CountingEstimator.fits = 0
        # The entry lives as long as the estimator that made it
        # (``_cache_fit``): held here, where it used to outlive the
        # statement only as cyclic garbage under ``structural_hash``.
        e1, e2 = CountingEstimator(lam=1e-3), CountingEstimator(lam=1e-3)
        p1 = e1.with_data(X.copy(), Y.copy()).fit()
        p2 = e2.with_data(X.copy(), Y.copy()).fit()
        assert CountingEstimator.fits == 1
        out1 = np.asarray(p1.apply(X).get())
        out2 = np.asarray(p2.apply(X).get())
        np.testing.assert_allclose(out1, out2)

    def test_different_data_refits(self):
        X, Y = _data(seed=0)
        X2, Y2 = _data(seed=1)
        CountingEstimator.fits = 0
        CountingEstimator(lam=1e-3).with_data(X, Y).fit()
        CountingEstimator(lam=1e-3).with_data(X2, Y2).fit()
        assert CountingEstimator.fits == 2


class TestDiskCache:
    def test_second_session_hits_disk(self, tmp_path, monkeypatch):
        monkeypatch.setenv("KEYSTONE_CACHE_DIR", str(tmp_path))
        X, Y = _data()
        CountingEstimator.fits = 0

        PipelineEnv.reset()
        p = CountingEstimator(lam=1e-3).with_data(X.copy(), Y.copy()).fit()
        ref = np.asarray(p.apply(X).get())
        assert CountingEstimator.fits == 1
        assert any(f.endswith(".fit.pkl") for f in os.listdir(tmp_path))

        PipelineEnv.reset()  # a "new process" as far as session state goes
        p2 = CountingEstimator(lam=1e-3).with_data(X.copy(), Y.copy()).fit()
        assert CountingEstimator.fits == 1  # served from disk
        np.testing.assert_allclose(np.asarray(p2.apply(X).get()), ref)

    def test_corrupt_entry_degrades_to_miss(self, tmp_path, monkeypatch):
        monkeypatch.setenv("KEYSTONE_CACHE_DIR", str(tmp_path))
        X, Y = _data()
        CountingEstimator.fits = 0
        PipelineEnv.reset()
        CountingEstimator(lam=1e-3).with_data(X.copy(), Y.copy()).fit()
        (entry,) = [f for f in os.listdir(tmp_path) if f.endswith(".fit.pkl")]
        (tmp_path / entry).write_bytes(b"corrupt")
        PipelineEnv.reset()
        CountingEstimator(lam=1e-3).with_data(X.copy(), Y.copy()).fit()
        assert CountingEstimator.fits == 2  # refit, no crash

    def test_malicious_entry_rejected(self, tmp_path):
        """A planted pickle whose payload resolves a non-allowlisted callable
        (the classic ``os.system`` reduce) must degrade to a miss, not run."""
        import pickle

        from keystone_tpu.workflow.disk_cache import DiskFitCache

        class Evil:
            def __reduce__(self):
                return (os.system, ("echo pwned > /dev/null",))

        cache = DiskFitCache(str(tmp_path / "store"))
        path = cache._path("deadbeef")
        with open(path, "wb") as f:
            pickle.dump(Evil(), f)
        assert cache.get("deadbeef") is None  # rejected and dropped
        assert not os.path.exists(path)

    def test_unimported_module_never_imported_by_cache_read(self, tmp_path):
        """find_class must refuse to IMPORT unknown modules — even resolving
        one runs its top-level code, so rejection has to come first."""
        import pickle
        import pickletools  # stdlib, importable, NOT in sys.modules' deps

        from keystone_tpu.workflow.disk_cache import DiskFitCache

        # Hand-craft a pickle whose GLOBAL names a module that is importable
        # but not yet imported; loading must miss without importing it.
        victim = "antigravity"  # stdlib easter egg; never imported by us
        payload = (
            b"\x80\x04" + b"c" + victim.encode() + b"\nfly\n" + b"."
        )  # proto4, GLOBAL antigravity.fly, STOP
        cache = DiskFitCache(str(tmp_path / "store"))
        with open(cache._path("k"), "wb") as f:
            f.write(payload)
        assert cache.get("k") is None
        assert victim not in sys.modules

    def test_gadget_chain_callables_rejected(self, tmp_path):
        """Allowlisted-module FUNCTIONS (numpy.load, functools.partial) are
        denied — only enumerated reconstructors and classes resolve."""
        import pickle

        from keystone_tpu.workflow.disk_cache import DiskFitCache

        class NumpyLoadGadget:
            def __reduce__(self):
                import numpy

                return (numpy.load, ("/nonexistent.npy",))

        class PartialGadget:
            def __reduce__(self):
                import functools

                return (functools.partial, (print,))

        class MemmapGadget:
            def __reduce__(self):
                import numpy

                target = str(tmp_path / "victim.bin")
                return (numpy.memmap, (target, "uint8", "w+", 0, (1,)))

        cache = DiskFitCache(str(tmp_path / "store"))
        gadgets = (NumpyLoadGadget(), PartialGadget(), MemmapGadget())
        for i, evil in enumerate(gadgets):
            with open(cache._path(f"g{i}"), "wb") as f:
                pickle.dump(evil, f)
            assert cache.get(f"g{i}") is None, type(evil).__name__
        # The memmap constructor must never have run (no file created).
        assert not (tmp_path / "victim.bin").exists()

    def test_restricted_unpickler_roundtrips_real_transformers(self, tmp_path):
        """The allowlist must not break the normal path: a fitted keystone
        transformer holding jax/numpy state loads back through it."""
        from keystone_tpu.nodes.stats import StandardScaler
        from keystone_tpu.workflow.disk_cache import DiskFitCache

        X = np.random.default_rng(0).normal(size=(32, 4)).astype(np.float32)
        fitted = StandardScaler().fit(X)
        cache = DiskFitCache(str(tmp_path / "store"))
        cache.put("k", fitted)
        loaded = cache.get("k")
        assert loaded is not None
        np.testing.assert_allclose(
            np.asarray(loaded.apply_batch(X)), np.asarray(fitted.apply_batch(X))
        )

    def test_cache_dir_created_private(self, tmp_path):
        from keystone_tpu.workflow.disk_cache import DiskFitCache

        root = tmp_path / "fresh"
        DiskFitCache(str(root))
        assert (root.stat().st_mode & 0o777) == 0o700

    @pytest.mark.slow
    def test_cross_process_reuse(self, tmp_path):
        """The VERDICT regression: a second *process* skips every refit."""
        script = textwrap.dedent(
            """
            import logging, sys
            import numpy as np
            import jax
            jax.config.update("jax_platforms", "cpu")
            logging.basicConfig(level=logging.INFO)
            from keystone_tpu.nodes.learning import LeastSquaresEstimator
            from keystone_tpu.nodes.stats import StandardScaler

            rng = np.random.default_rng(0)
            X = rng.normal(size=(512, 32)).astype(np.float32)
            Y = rng.normal(size=(512, 4)).astype(np.float32)
            p = StandardScaler().with_data(X).and_then(
                LeastSquaresEstimator(lam=1e-3), X, Y
            ).fit()
            out = np.asarray(p.apply(X).get())
            np.save(sys.argv[1], out)
            """
        )
        from keystone_tpu.utils.platform import cpu_mesh_env

        env = cpu_mesh_env(8)
        env["KEYSTONE_CACHE_DIR"] = str(tmp_path)
        outs, hits = [], []
        for i in range(2):
            out_npy = str(tmp_path / f"out{i}.npy")
            proc = subprocess.run(
                [sys.executable, "-c", script, out_npy],
                env=env,
                capture_output=True,
                text=True,
                timeout=300,
                cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            )
            assert proc.returncode == 0, proc.stderr[-2000:]
            outs.append(np.load(out_npy))
            hits.append(proc.stderr.count("disk fit cache: hit"))
        np.testing.assert_allclose(outs[0], outs[1], rtol=1e-5, atol=1e-5)
        assert hits[0] == 0
        # Both estimators (scaler + solver) served from the store, no refits.
        assert hits[1] == 2


class TestDiskCacheTrim:
    def test_evicts_least_recently_used(self, tmp_path):
        import time

        from keystone_tpu.workflow.disk_cache import DiskFitCache

        X, _ = _data()
        one = len(__import__("pickle").dumps(_MatTransformer(X)))
        # Budget fits exactly one entry, so the eviction ORDER is pinned:
        # the stale entry goes, the freshly-used one survives.
        cache = DiskFitCache(str(tmp_path), max_bytes=int(one * 1.5))
        cache.put("aaa", _MatTransformer(X))
        time.sleep(0.05)
        assert cache.get("aaa") is not None  # refreshes recency
        time.sleep(0.05)
        cache.put("bbb", _MatTransformer(X))
        time.sleep(0.05)
        assert cache.get("bbb") is not None
        cache.put("ccc", _MatTransformer(X))  # trims: aaa is now the LRU
        remaining = {
            f for f in os.listdir(tmp_path) if f.endswith(".fit.pkl")
        }
        assert "ccc.fit.pkl" in remaining
        assert "aaa.fit.pkl" not in remaining

    def test_no_trim_under_budget(self, tmp_path):
        from keystone_tpu.workflow.disk_cache import DiskFitCache

        cache = DiskFitCache(str(tmp_path), max_bytes=1 << 30)
        X, _ = _data()
        cache.put("aaa", _MatTransformer(X))
        cache.put("bbb", _MatTransformer(X))
        assert cache.get("aaa") is not None and cache.get("bbb") is not None


class TestCrashSafety:
    """The checkpoint/resume substrate (ISSUE 3 satellite): a process
    killed mid-write must never leave a truncated entry a later get()
    trips over."""

    def test_put_is_atomic_no_partial_entry_visible(self, tmp_path):
        """Simulate a kill mid-write: a pickler that dies halfway through
        dump leaves ONLY a temp file — the addressed entry never exists in
        a partial state."""
        import pickle
        from unittest import mock

        from keystone_tpu.workflow.disk_cache import DiskCache

        cache = DiskCache(str(tmp_path / "store"))
        payload = {"W": np.zeros((64, 64), dtype=np.float32)}

        class Killed(BaseException):
            pass

        def dying_dump(obj, f):
            f.write(pickle.dumps(obj)[:100])  # partial bytes on disk...
            raise Killed()  # ...then the "kill"

        with mock.patch.object(pickle, "dump", dying_dump):
            with pytest.raises(Killed):
                cache.put("ck", payload)
        assert cache.get("ck") is None  # entry never became addressable
        assert not os.path.exists(cache._path("ck"))

    def test_overwrite_is_atomic_old_entry_survives_killed_rewrite(
        self, tmp_path
    ):
        import pickle
        from unittest import mock

        from keystone_tpu.workflow.disk_cache import DiskCache

        cache = DiskCache(str(tmp_path / "store"))
        cache.put("ck", {"chunks_done": 4}, overwrite=True)

        class Killed(BaseException):
            pass

        def dying_dump(obj, f):
            raise Killed()

        with mock.patch.object(pickle, "dump", dying_dump):
            with pytest.raises(Killed):
                cache.put("ck", {"chunks_done": 6}, overwrite=True)
        # The PREVIOUS complete checkpoint is still there, readable.
        assert cache.get("ck") == {"chunks_done": 4}

    def test_overwrite_replaces_and_default_put_dedups(self, tmp_path):
        from keystone_tpu.workflow.disk_cache import DiskCache

        cache = DiskCache(str(tmp_path / "store"))
        cache.put("k", 1)
        cache.put("k", 2)  # content-addressed default: first write wins
        assert cache.get("k") == 1
        cache.put("k", 3, overwrite=True)
        assert cache.get("k") == 3

    def test_stale_tmps_swept_fresh_ones_kept(self, tmp_path):
        import time

        from keystone_tpu.workflow.disk_cache import DiskCache

        root = tmp_path / "store"
        DiskCache(str(root))  # create
        stale = root / "deadbeef.pkl.tmp"
        fresh = root / "inflight.pkl.tmp"
        other = root / "cafe.fit.pkl.tmp"  # a CO-RESIDENT store's orphan
        for f in (stale, fresh, other):
            f.write_bytes(b"partial")
        old = time.time() - 2 * DiskCache._TMP_MAX_AGE_S
        os.utime(stale, (old, old))
        os.utime(other, (old, old))
        DiskCache(str(root))  # a new store sweeps its root
        assert not stale.exists()  # own orphan gone
        assert fresh.exists()  # live concurrent writer's temp untouched
        assert other.exists()  # suffix-scoped: another store's, not ours

    def test_suffixes_namespace_coresident_stores(self, tmp_path):
        from keystone_tpu.workflow.disk_cache import DiskCache, DiskFitCache

        root = str(tmp_path / "store")
        ckpt = DiskCache(root, suffix=".ckpt.pkl")
        fits = DiskFitCache(root)
        ckpt.put("same-key", {"kind": "checkpoint"})
        fits.put("same-key", {"kind": "fit"})
        assert ckpt.get("same-key") == {"kind": "checkpoint"}
        assert fits.get("same-key") == {"kind": "fit"}


class TestConcurrentWriters:
    @pytest.mark.slow
    def test_parallel_processes_share_one_store(self, tmp_path):
        """Four processes share one cache dir, two per problem — the pairs
        race the SAME content key's tmp+rename commit while the pairs
        differ. Every process's second session must log a real store hit
        (not just reproduce values by refitting), entries must end corrupt-
        free, and a distinct-key pair must coexist with the racing pair."""
        script = textwrap.dedent(
            """
            import logging, os, sys
            import numpy as np
            import jax
            jax.config.update("jax_platforms", "cpu")
            logging.basicConfig(level=logging.INFO)
            from keystone_tpu.nodes.learning import LeastSquaresEstimator
            from keystone_tpu.workflow import PipelineEnv

            seed = int(sys.argv[1])
            rng = np.random.default_rng(seed)
            X = rng.normal(size=(128, 16)).astype(np.float32)
            W = rng.normal(size=(16, 2)).astype(np.float32)
            Y = X @ W
            p = LeastSquaresEstimator(lam=1e-4).with_data(X, Y).fit()
            out1 = np.asarray(p.apply(X).get())
            PipelineEnv.reset()  # second "session": must hit the store
            p2 = LeastSquaresEstimator(lam=1e-4).with_data(X.copy(), Y.copy()).fit()
            out2 = np.asarray(p2.apply(X).get())
            np.testing.assert_allclose(out2, out1, rtol=1e-6)
            resid = np.linalg.norm(out1 - Y) / np.linalg.norm(Y)
            assert resid < 1e-3, resid
            print("WRITER_OK", seed)
            """
        )
        from keystone_tpu.utils.platform import cpu_mesh_env

        env = cpu_mesh_env(2)
        env["KEYSTONE_CACHE_DIR"] = str(tmp_path)
        procs = []
        try:
            procs = [
                subprocess.Popen(
                    [sys.executable, "-c", script, str(seed)],
                    env=env,
                    cwd=os.path.dirname(
                        os.path.dirname(os.path.abspath(__file__))
                    ),
                    stdout=subprocess.PIPE,
                    stderr=subprocess.PIPE,
                    text=True,
                )
                for seed in (0, 0, 1, 1)  # pairs race the same key
            ]
            for p in procs:
                out, err = p.communicate(timeout=300)
                assert p.returncode == 0, err[-2000:]
                assert "WRITER_OK" in out
                # The read path must actually serve the entry — a refit
                # would reproduce the values and hide a dead get().
                assert "disk fit cache: hit" in err
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
                    p.communicate()
        entries = [f for f in os.listdir(tmp_path) if f.endswith(".fit.pkl")]
        assert len(entries) == 2  # one entry per distinct problem
        assert not [f for f in os.listdir(tmp_path) if f.endswith(".tmp")]


class TestNodeOptimizationMemo:
    def test_concrete_estimator_stable_across_passes(self):
        from keystone_tpu.workflow.operators import EstimatorOperator
        from keystone_tpu.workflow.rules import NodeOptimizationRule

        X, Y = _data(n=256, d=16)
        p = LeastSquaresEstimator(lam=1e-3).with_data(X, Y)
        rule = NodeOptimizationRule()
        g1 = rule.apply(p.graph, [p.sink])
        g2 = rule.apply(p.graph, [p.sink])
        c1 = [
            op.estimator
            for op in g1.operators.values()
            if isinstance(op, EstimatorOperator)
            and not isinstance(op.estimator, LeastSquaresEstimator)
        ]
        c2 = [
            op.estimator
            for op in g2.operators.values()
            if isinstance(op, EstimatorOperator)
            and not isinstance(op.estimator, LeastSquaresEstimator)
        ]
        assert c1 and c2 and c1[0] is c2[0]


def test_trust_all_knob_fails_closed_on_falsy_spellings(tmp_path, monkeypatch):
    """KEYSTONE_CACHE_TRUST_ALL is a security knob: only the strict "1"
    disables the restricted unpickler; "off"/"disabled"/"0" keep it."""
    import glob
    import pickle

    import numpy as np

    from keystone_tpu.workflow.disk_cache import DiskFitCache

    cache = DiskFitCache(str(tmp_path))
    key = "deadbeef" * 8
    cache.put(key, np.arange(4.0))
    entry = glob.glob(str(tmp_path / "**" / "*.pkl"), recursive=True)[0]

    class Evil:
        def __reduce__(self):
            return (eval, ("['pwned']",))

    for spelling, expect_blocked in [
        ("off", True),
        ("disabled", True),
        ("0", True),
        ("1", False),
    ]:
        with open(entry, "wb") as f:
            pickle.dump(Evil(), f)
        monkeypatch.setenv("KEYSTONE_CACHE_TRUST_ALL", spelling)
        got = cache.get(key)  # rejected entries -> dropped, miss (None)
        assert (got is None) == expect_blocked, (spelling, got)
