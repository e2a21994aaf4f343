"""Graph operators — the node payloads of the pipeline DAG.

Ref: src/main/scala/workflow/Operator.scala (TransformerOperator,
EstimatorOperator, DelegatingOperator, DatasetOperator, DatumOperator)
[unverified]. Expressions in the reference are lazy wrappers over RDDs; here
an "expression" value is simply a batch (jax/numpy array or host sequence), a
single datum, or a fitted Transformer.
"""

from __future__ import annotations

from contextlib import contextmanager
from typing import Any, List, Sequence

import jax.numpy as jnp


class Operator:
    """Base operator. ``execute`` consumes evaluated dependency values."""

    def execute(self, deps: Sequence[Any]) -> Any:
        raise NotImplementedError

    def signature(self) -> Any:
        """Identity key used for structural prefix hashing. The id fallback
        carries the UNSTABLE poison so heap addresses can never leak into a
        cross-process digest (a recycled id must not produce a disk hit)."""
        from keystone_tpu.workflow.fingerprint import UNSTABLE

        return ("op", id(self), UNSTABLE)

    def prefix_hash(self, dep_hashes) -> int:
        """Structural hash of this node given its dependency prefix hashes."""
        return hash((self.signature(), tuple(dep_hashes)))

    def prefix_digest(self, dep_digests):
        """Content-stable digest of this node's prefix (the cross-process
        analog of ``prefix_hash``), or None when any part is id-based."""
        from keystone_tpu.workflow.fingerprint import digest_tree

        if any(d is None for d in dep_digests):
            return None
        return digest_tree((self.signature(), tuple(dep_digests)))

    def pinned_objects(self):
        """Objects whose id() feeds this operator's signature. Cache entries
        keyed on prefixes through this node hold strong references to these so
        CPython id reuse can never alias a stale cache entry."""
        return ()

    def label(self) -> str:
        return type(self).__name__


@contextmanager
def placed_batch(data: Any):
    """A host batch that several walks of one fit read, on the device for
    as long as the ``with`` lasts: one upload and one fingerprint where
    every walk's ``DatasetOperator`` would hash it and every chain's
    program upload it again.

    Yields what the walks should read. A numeric host array is placed by
    ``DatasetOperator.execute``'s rule (rows over the default mesh where
    they divide it, a plain ``device_put`` on one device); what that rule
    leaves on the host for a chain to stage (a batch below
    ``config.shard_min_rows``, rows that do not divide the mesh), what is
    not a numeric host array and what is on a device already come back
    untouched. The placed array is the caller's: no program takes it as a
    donated argument, and nothing here outlives the ``with`` — a second
    fit of the same host array uploads and hashes again, as a decoder's
    next output would.

    The upload is dispatched first and the host bytes are hashed while it
    is in flight. ``DatasetOperator.signature`` and
    ``fingerprint.batch_fingerprint`` read that fingerprint for the placed
    array, so every prefix hash and disk-cache key is the host array's.
    One ``data.place`` span (``bytes``: what crossed to the device;
    ``sharded``: 1 where each device was put its own rows).
    """
    import jax

    from keystone_tpu.config import config
    from keystone_tpu.utils.mesh import (
        host_batch_shard_class,
        is_numeric_host_batch,
    )
    from keystone_tpu.utils.metrics import active_tracer, span_of
    from keystone_tpu.workflow import fingerprint

    klass = host_batch_shard_class(data) if config.shard_data_batches else "inert"
    if not is_numeric_host_batch(data) or klass in ("small", "pad"):
        yield data
        return
    with span_of(active_tracer(), "data.place", "pipeline",
                 bytes=int(data.nbytes), rows=int(data.shape[0]),
                 sharded=int(klass == "shard")):
        if klass == "shard":  # the operator's own placement, counted as its
            placed = DatasetOperator(data).execute([])
        else:
            placed = jax.device_put(data)
        fingerprint.PLACED[id(placed)] = fingerprint.array_fingerprint(data)
    try:
        yield placed
    finally:
        del fingerprint.PLACED[id(placed)]


class DatasetOperator(Operator):
    """A constant batch of data spliced into the graph (the RDD analog).

    Array batches are row-sharded over the default mesh on execution, so
    the jittable transformer chain downstream runs data-parallel across
    chips — the per-partition map of the reference. Divisible batches are
    placed here with the explicit data sharding; non-divisible batches are
    deferred to the fused chain's mask-pad path (``Transformer.batch_call``
    pads onto the mesh and trims, the pad-inert idiom), so a batch that
    doesn't divide the mesh no longer silently degrades to single-device.
    The only surviving fallback — batches below ``config.shard_min_rows``
    — is counted in the metrics registry (``sharding.fallback_small_batch``)
    so it is visible, never silent.
    """

    def __init__(self, data: Any):
        self.data = data

    def execute(self, deps):
        import logging

        import jax

        from keystone_tpu.config import config

        data = self.data
        if not config.shard_data_batches:
            return data
        # One classifier shared with batch_layout and the KG103 lint
        # (utils.mesh.host_batch_shard_class), so placement, lowering,
        # and static analysis can never drift apart. A jax.Array already
        # has a placement (explicit or default) that we must not
        # override; non-numeric arrays belong to host transformers —
        # both are "inert" here.
        from keystone_tpu.utils.mesh import (
            data_sharding,
            host_batch_shard_class,
        )
        from keystone_tpu.utils.metrics import sharding_counters

        klass = host_batch_shard_class(data)
        if klass == "inert":
            return data
        if klass == "small":
            # The ONLY surviving single-device fallback: placement overhead
            # beats the win below the row floor. Counted AND logged so a
            # fit that quietly ran narrow is visible in the registry.
            sharding_counters.bump("fallback_small_batch")
            logging.getLogger("keystone_tpu").info(
                "batch of %d rows is below shard_min_rows=%d; running this "
                "dataset single-device",
                data.shape[0],
                config.shard_min_rows,
            )
            return data
        if klass == "pad":
            # Deferred, not dropped: jax refuses an uneven device_put, so
            # the fused chain's sharded call mask-pads this batch onto the
            # mesh (mesh.SpecLayout.pad_put) and trims the pad rows back
            # out — downstream row counts are unchanged and the chain
            # still lowers with explicit shardings.
            sharding_counters.bump("batches_deferred_pad")
            return data
        sharding_counters.bump("batches_sharded")
        return jax.device_put(data, data_sharding())

    def signature(self):
        """Content fingerprint for numeric arrays, id fallback otherwise.
        Content identity means a rerun — or another process — that splices
        byte-identical data shares cached fits downstream. A host array is
        hashed once per operator, and every walk makes a new operator; a
        batch that ``placed_batch`` put on the device was hashed once, on
        the host, and answers with that fingerprint for every walk."""
        sig = getattr(self, "_sig_cache", None)
        if sig is None:
            import jax
            import numpy as np

            from keystone_tpu.workflow.fingerprint import (
                UNSTABLE,
                PLACED,
                array_fingerprint,
                host_values,
            )

            from keystone_tpu.config import config

            data = self.data
            if isinstance(data, jax.Array):
                placed = PLACED.get(id(data))
                if placed is not None:
                    self._sig_cache = ("dataset", placed)
                    return self._sig_cache
                if data.nbytes > config.fingerprint_max_bytes:
                    # Sampled hashing would still need the full D2H copy
                    # for a device array; not worth it.
                    self._sig_cache = ("dataset", id(self.data), UNSTABLE)
                    return self._sig_cache
                data = host_values(data)
            if isinstance(data, np.ndarray) and data.dtype.kind in "biufc":
                # array_fingerprint switches to a bounded chunk-sampled
                # digest above config.fingerprint_max_bytes, so huge fit
                # inputs stay content-addressed at fixed cost.
                sig = ("dataset", array_fingerprint(data))
            elif isinstance(data, (list, tuple)) and data:
                from keystone_tpu.workflow.fingerprint import text_fingerprint

                fp = text_fingerprint(data)
                sig = (
                    ("dataset", fp)
                    if fp is not None
                    else ("dataset", id(self.data), UNSTABLE)
                )
            else:
                sig = ("dataset", id(self.data), UNSTABLE)
            self._sig_cache = sig
        return sig

    def pinned_objects(self):
        return (self.data,)

    def label(self):
        return "Dataset"


class DatumOperator(Operator):
    """A single constant datum."""

    def __init__(self, datum: Any):
        self.datum = datum

    def execute(self, deps):
        return self.datum

    def signature(self):
        from keystone_tpu.workflow.fingerprint import UNSTABLE

        return ("datum", id(self.datum), UNSTABLE)

    def pinned_objects(self):
        return (self.datum,)

    def label(self):
        return "Datum"


class TransformerOperator(Operator):
    """Applies a Transformer to its single input batch."""

    def __init__(self, transformer):
        self.transformer = transformer

    def execute(self, deps):
        return self.transformer.batch_call(deps[0])

    def signature(self):
        return ("transformer", self.transformer.signature())

    def prefix_hash(self, dep_hashes):
        # Delegated so that a fused chain hashes identically to the unfused
        # chain it replaced (FusedTransformer folds stage-by-stage).
        return self.transformer.chain_hash(dep_hashes[0])

    def prefix_digest(self, dep_digests):
        if dep_digests[0] is None:
            return None
        return self.transformer.chain_digest(dep_digests[0])

    def pinned_objects(self):
        return (self.transformer,)

    def label(self):
        # A fused chain names its stages: the profiler/trace attribution
        # row for one XLA program should say WHICH operators it fused,
        # not the anonymous wrapper class.
        stages = getattr(self.transformer, "stages", None)
        if stages:
            return "Fused(" + "|".join(type(s).__name__ for s in stages) + ")"
        return type(self.transformer).__name__


class EstimatorOperator(Operator):
    """Fits an Estimator/LabelEstimator on its input(s); the value produced is
    the fitted Transformer (a TransformerExpression in reference terms)."""

    def __init__(self, estimator):
        self.estimator = estimator

    def execute(self, deps):
        return self.estimator.fit(*deps)

    def signature(self):
        """Content-stable when the estimator's signature is (class +
        hyperparams, see pipeline.Estimator.signature); id-keyed otherwise.
        Memoized at first use so an estimator that mutates its own fields
        while fitting keeps one identity for this node — otherwise the
        post-fit signature could never hit the entry cached under the
        pre-fit one. The estimator stays pinned either way, so id-based
        fields can never alias across its lifetime."""
        sig = getattr(self, "_sig_cache", None)
        if sig is None:
            sig_fn = getattr(self.estimator, "signature", None)
            if sig_fn is not None:
                sig = ("estimator", sig_fn())
            else:
                from keystone_tpu.workflow.fingerprint import UNSTABLE

                sig = ("estimator", id(self.estimator), UNSTABLE)
            self._sig_cache = sig
        return sig

    def pinned_objects(self):
        return (self.estimator,)

    def label(self):
        return type(self.estimator).__name__ + ".fit"


class DelegatingOperator(Operator):
    """Applies the fitted transformer produced by an estimator node.

    deps = [fitted_transformer, input_batch].
    Ref: workflow/Operator.scala DelegatingOperator [unverified].
    """

    def execute(self, deps):
        fitted, x = deps
        return fitted.batch_call(x)

    def signature(self):
        # The behaviour is fully determined by the estimator dep's hash, so a
        # shared constant signature keeps structurally-equal graphs equal.
        return ("delegating",)

    def label(self):
        return "Delegating"


class GatherOperator(Operator):
    """Concatenates branch outputs along the feature (last) axis.

    Ref: Pipeline.gather building a gather node over branch sinks
    (workflow/Pipeline.scala) [unverified]. On TPU this lowers to one XLA
    concatenate, which typically fuses with downstream consumers.
    """

    def execute(self, deps: Sequence[Any]):
        return jnp.concatenate([jnp.asarray(d) for d in deps], axis=-1)

    def signature(self):
        return ("gather",)

    def label(self):
        return "Gather"
