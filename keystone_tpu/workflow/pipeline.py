"""Pipeline API: Transformer / Estimator / LabelEstimator / Pipeline.

Ref: src/main/scala/workflow/{Pipeline,Transformer,Estimator,LabelEstimator,
PipelineDataset}.scala [unverified]. The algebra is preserved:

- ``Transformer`` — a pure per-datum (liftable to per-batch) function; itself
  composable like a one-node pipeline.
- ``Estimator.fit(data) -> Transformer``; ``with_data`` splices a lazy fit
  into a graph.
- ``pipeline.and_then(...)`` composes; ``Pipeline.gather([...])`` merges
  branches by feature concatenation.
- Applying a pipeline is lazy: you get a ``PipelineDataset`` handle; ``get()``
  optimizes the graph and executes it.

The execution difference from the reference: instead of staging RDD
transformations, contiguous jittable transformer chains are fused by the
optimizer into single XLA computations (see workflow/optimizer.py), and batch
values are (possibly sharded) device arrays.
"""

from __future__ import annotations

import functools
from contextlib import nullcontext
from typing import Any, Callable, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from keystone_tpu.utils.metrics import stage_scope
from keystone_tpu.workflow.graph import (
    Graph,
    GraphId,
    NodeId,
    SourceId,
    fresh_source_id,
)
from keystone_tpu.workflow.operators import (
    DatasetOperator,
    DatumOperator,
    DelegatingOperator,
    EstimatorOperator,
    GatherOperator,
    Operator,
    TransformerOperator,
)


def _is_array(x: Any) -> bool:
    return isinstance(x, (jax.Array, np.ndarray))


# ---------------------------------------------------------------------------
# Programs that take a transformer's arrays as arguments
# ---------------------------------------------------------------------------


# Per-instance memos of a transformer (jitted callables; the donation memo's
# keys carry the live mesh): never pickled, flattened or compared.
_JIT_CACHES = ("_jit_cache", "_shard_jit_cache", "_donate_ok_cache")


@functools.lru_cache(maxsize=None)
def _program(name: str, layout=None, donate: bool = False):
    """The jitted ``(transformer, X) -> transformer.apply_batch(X)``. Every
    ``Transformer`` is a pytree (its ``array_fields`` the children, its
    other fields the static part), so ``jax.jit`` keys on the tree's
    structure and the arrays' shapes and dtypes: the compiled program holds
    none of the arrays, and every transformer of one structure shares it,
    with arrays or with none (which take this path:
    ``Transformer.shares_program``). One callable a ``name`` because the
    HLO module is named after the function, and the device trace is read by
    module. With ``layout`` it is the sharded lowering (arrays replicated,
    rows sharded in and out)."""

    def apply(transformer, X):
        # A chain's walk scopes each of its steps; a transformer that is a
        # program by itself is its one stage.
        with (nullcontext() if isinstance(transformer, FusedTransformer)
              else stage_scope(transformer)):
            if layout is None:
                return transformer.apply_batch(X)
            return transformer.apply_sharded(X, layout)

    apply.__name__ = ("apply_" + name)[:96]
    if layout is None:
        return jax.jit(apply)
    return layout.jit(apply, donate_argnums=(1,) if donate else (), params=True)


class _Bound:
    """``_program`` with its transformer filled in: what ``_jitted()``
    hands out, with the parts of a jitted callable its callers use. Made
    anew at each call (a cached one would tie the transformer into a
    cycle), and it counts its calls and the bytes it hands over
    (``program_counters``: what a constant would not show)."""

    __slots__ = ("program", "transformer")

    def __init__(self, program, transformer: "Transformer"):
        self.program = program
        self.transformer = transformer

    def __call__(self, X):
        from keystone_tpu.utils.metrics import program_counters

        program_counters.bump("shared_program_calls")
        program_counters.bump("argument_bytes", sum(
            int(leaf.nbytes)
            for leaf in jax.tree_util.tree_leaves(self.transformer)
        ))
        return self.program(self.transformer, X)

    def lower(self, X):
        return self.program.lower(self.transformer, X)

    def _cache_size(self) -> int:
        return self.program._cache_size()


class _Closure:
    """The jitted ``apply_batch`` of a transformer that shares no program:
    the same parts as ``_Bound``, and its calls counted beside
    ``_Bound``'s. Kept on the transformer, which is what finds it again."""

    __slots__ = ("program",)

    def __init__(self, program):
        self.program = program

    def __call__(self, X):
        from keystone_tpu.utils.metrics import program_counters

        program_counters.bump("closure_program_calls")
        return self.program(X)

    def lower(self, X):
        return self.program.lower(X)

    def _cache_size(self) -> int:
        return self.program._cache_size()


# A fused chain's largest intermediate may take this share of the device's
# memory (``device_hbm_bytes()``); a chain whose largest would take more
# runs in row tiles that take at most this (``FusedTransformer.row_tiling``).
# The first value tried, and not a swept optimum: on a v5e the one chain that
# tiles (cifar-fit's, 6,250 rows) ran 1.405 s at an eighth (36-row tiles),
# 1.298 s at a quarter (72) and 2.798 s at a sixteenth (18): PERF.md, PR 32.
_TILE_HBM_SHARE = 8


def _steps(stages):
    """A chain's walk: ``(stage, taken)`` pairs in order, ``taken`` the
    stages right behind ``stage`` that it runs itself (``Transformer.takes``;
    mostly none). What applies a chain and what prices it take these steps."""
    steps, i = [], 0
    while i < len(stages):
        taken = stages[i + 1:i + 1 + stages[i].takes(stages[i + 1:])]
        steps.append((stages[i], taken))
        i += 1 + len(taken)
    return steps


def _walk(stages, X):
    """``X`` after every step of the chain, in order."""
    out = []
    for stage, taken in _steps(stages):
        with stage_scope(stage, taken):
            X = stage.apply_with(taken, X) if taken else stage.apply_batch(X)
        out.append(X)
    return out


def _intermediates(chain, X):
    """The outputs of every step of ``chain`` but the last (which is no
    intermediate: tiles or none, it is written whole)."""
    return _walk(chain.stages, X)[:-1]


@functools.lru_cache(maxsize=None)
def _row_tiling(treedef, leaves, x, budget: int):
    """``(tile_rows, tiles)`` for the chain that ``treedef`` and ``leaves``
    (its arrays' shapes) describe, applied to a batch of ``x``'s shape and
    dtype, or None where what every step holds fits ``budget`` bytes. Shapes
    only: the stages' outputs are propagated with ``jax.eval_shape``, as
    ``workflow/analysis.py`` prices a chain; found again by structure, as
    the chain's program is."""
    chain = jax.tree_util.tree_unflatten(treedef, leaves)
    n, row_bytes = int(x.shape[0]), 0
    between = list(jax.eval_shape(_intermediates, chain, x))
    # A step holds its output (the last step's is no intermediate) and
    # what it says it lays out for a kernel it runs (``scratch_with``).
    for (stage, taken), given, out in zip(
            _steps(chain.stages), [x] + between, between + [()]):
        held = jax.tree_util.tree_leaves(out)
        if taken:
            held = held + list(stage.scratch_with(taken, given))
        if any(o.ndim < 1 or o.shape[0] != n for o in held):
            return None  # rows do not stay rows: nothing to cut along
        row_bytes = max(row_bytes, sum(
            int(np.prod(o.shape[1:], dtype=np.int64)) * o.dtype.itemsize
            for o in held
        ))
    if n * row_bytes <= budget:
        return None
    tiles = -(-n // max(1, budget // row_bytes))
    return -(-n // tiles), tiles


def _hashes_by_value(value: Any) -> bool:
    """Is ``hash(value)`` a function of what ``value`` holds? Not where it
    does not hash (a list, an array), and not where it hashes by identity
    (a function, a bound method, an object with the default ``__hash__``):
    a shared program outlives the transformer, and such a key would stay
    in jit's cache for the life of the process and never be met again. A
    class counts as its own value."""
    if isinstance(value, (tuple, frozenset)):
        return all(_hashes_by_value(v) for v in value)
    if isinstance(value, type):
        return True
    if callable(value) or type(value).__hash__ in (None, object.__hash__):
        return False
    try:
        hash(value)
    except TypeError:
        return False
    return True


# ---------------------------------------------------------------------------
# Transformer
# ---------------------------------------------------------------------------


class Transformer:
    """A pure function applied per-datum, lifted to batches.

    Subclasses override ``apply_batch`` (device code operating on a batch with
    a leading example axis — the common case, jitted and fused by the
    executor) or ``apply`` (per-datum host code; set ``jittable = False``).

    Ref: workflow/Transformer.scala — per-datum ``apply`` lifted to RDDs via
    mapPartitions [unverified]. Here the lift is vectorization: the batch IS
    the unit of execution, which is what the MXU wants.
    """

    jittable: bool = True

    # Output row i depends on input row i alone AND output rows == input
    # rows — the contract that makes bucket-padding sound (pad rows cannot
    # perturb real outputs, and slicing [:n] recovers exactly them). True
    # for the per-datum-lifted common case; transformers that couple rows
    # (batch statistics at apply time) or fan rows out (Windower,
    # CenterCornerPatcher) set False, and the bucketed serving path refuses
    # them with serving.RowDependenceError.
    row_independent: bool = True

    # The attributes that hold this transformer's arrays, fitted or drawn
    # (or, for a chain, its stages): the children of the pytree every
    # transformer is, and the arguments of its program (``_program``), not
    # constants in it: the program does not grow with them and need not be
    # compiled again for other values. The other fields are the static
    # part. The rule (``shares_program``): where every one of them hashes
    # by value, arrays or none, the program is found by the transformer's
    # structure (class, static fields, the arrays' shapes and dtypes) and
    # shared by every transformer of that structure, in this fit and the
    # next; where one does not (an array not named here, a callable, an
    # object hashed by identity), ``apply_batch`` is jitted as the closure
    # it is and kept on the transformer. What ``apply_batch`` reads has to
    # be a field: a value read from elsewhere at trace time (``config``)
    # is resolved in ``__init__``.
    array_fields: tuple = ()

    def __init_subclass__(cls, **kwargs):
        super().__init_subclass__(**kwargs)
        jax.tree_util.register_pytree_node(
            cls, cls._tree_flatten, functools.partial(cls._tree_unflatten, cls)
        )

    def _tree_flatten(self):
        """(the ``array_fields``' values; what ``apply_batch`` reads beside
        them: the other fields, each with its type, so that 1, 1.0 and True
        are three programs). ``_sig`` is an identity, not an input."""
        skip = self.array_fields + _JIT_CACHES + ("_sig",)
        static = tuple(sorted(
            ((k, v, type(v)) for k, v in vars(self).items() if k not in skip),
            key=lambda field: field[0],
        ))
        return tuple(getattr(self, k) for k in self.array_fields), static

    @staticmethod
    def _tree_unflatten(cls, static, children) -> "Transformer":
        """A transformer of ``cls`` around ``children`` (inside a trace:
        tracers), with no signature and no program of its own."""
        made = object.__new__(cls)
        made.__dict__.update((k, v) for k, v, _type in static)
        made.__dict__.update(zip(cls.array_fields, children))
        return made

    def shares_program(self) -> bool:
        """Is this transformer's program found by its structure? Where
        every static field, of it and of the transformers among its
        children, hashes by value (``array_fields``' comment has the
        rule). Then its identity finds nothing, and nothing need keep it
        alive."""
        _leaves, treedef = jax.tree_util.tree_flatten(self)
        nodes = [treedef]
        while nodes:  # PyTreeDef's own hash leaves the static parts out
            node = nodes.pop()
            kind, static = node.node_data() or (None, None)
            if isinstance(kind, type) and issubclass(kind, Transformer):
                if not all(_hashes_by_value(v) for _k, v, _type in static):
                    return False
            nodes.extend(node.children())
        return True

    def _program_name(self) -> str:
        return type(self).__name__

    def apply(self, x: Any) -> Any:
        if _is_array(x) or jnp.isscalar(x):
            return self.batch_call(jnp.asarray(x)[None, ...])[0]
        raise NotImplementedError(
            f"{type(self).__name__} must override apply() for non-array data"
        )

    def takes(self, following: Sequence["Transformer"]) -> int:
        """How many of ``following``, the stages right behind this one in a
        fused chain, this one runs itself (``apply_with``): none, unless a
        transformer has one program for itself and its neighbours that
        keeps what lies between them off the device's memory. Asked of the
        stages' types and static fields, never of a value."""
        return 0

    def apply_with(self, taken: Sequence["Transformer"], X: Any) -> Any:
        """``X`` through this stage and the ``taken`` ones behind it."""
        raise NotImplementedError(
            f"{type(self).__name__} takes no stage behind it"
        )

    def scratch_with(self, taken: Sequence["Transformer"], x: Any) -> tuple:
        """The arrays, as shapes, that ``apply_with(taken, X)`` writes to
        the device's memory beside its output for an ``X`` of ``x``'s
        shape: what its kernel needs laid out in front of it. A chain's
        row-tile rule prices them as it prices the steps' outputs."""
        return ()

    def apply_batch(self, X: Any) -> Any:
        # Host-side default: per-datum loop. Device transformers override.
        if type(self).apply is Transformer.apply:
            # Neither method overridden — fail clearly instead of letting the
            # two defaults recurse into each other.
            raise NotImplementedError(
                f"{type(self).__name__} must override apply_batch() or apply()"
            )
        return [self.apply(x) for x in X]

    # -- execution ---------------------------------------------------------

    def batch_call(self, X: Any) -> Any:
        """Apply to a batch, via the cached jitted function when possible.

        With ``config.serve_buckets`` set (env KEYSTONE_SERVE_BUCKETS),
        array batches are rounded up the bucket ladder, padded, run at the
        bucket shape, and sliced — the jit cache then only ever sees ladder
        shapes, so variable-size traffic stops recompiling once the ladder
        is warm. Empty ladder = per-shape jit, exactly as before.

        Under ``config.shard_data_batches``, a batch carrying (or owed)
        the mesh's data-parallel layout lowers the WHOLE chain once with
        explicit ``in_shardings``/``out_shardings`` (``mesh.SpecLayout``)
        instead of inheriting whatever placement the input happened to
        carry — and a non-divisible host batch is mask-padded onto the
        mesh and trimmed, never silently run single-device.
        """
        if self.jittable and _is_array(X):
            from keystone_tpu.config import config

            if config.serve_buckets:
                from keystone_tpu.workflow.serving import bucketed_call

                return bucketed_call(self, X)
            if config.shard_data_batches:
                from keystone_tpu.utils.mesh import batch_layout

                layout = batch_layout(X)
                if layout is not None:
                    return self._sharded_call(X, layout)
            return self._jitted()(X)
        return self.apply_batch(X)

    def _jitted(self) -> Callable:
        if self.shares_program():
            return _Bound(_program(self._program_name()), self)
        fn = getattr(self, "_jit_cache", None)
        if fn is None:
            fn = _Closure(jax.jit(self.apply_batch))
            object.__setattr__(self, "_jit_cache", fn)
        return fn

    def apply_sharded(self, X, layout):
        """The chain body the sharded lowering traces — ``apply_batch``
        unless a transformer needs the mesh layout to pick a sharded
        kernel strategy (``FisherVector``'s Pallas backend wraps its
        kernel in ``shard_map`` on real TPU meshes; everywhere else the
        plain body partitions under GSPMD bit-identically)."""
        return self.apply_batch(X)

    #: Does this chain run a Pallas kernel? Drives the
    #: ``pallas_sharded_calls`` evidence counter on the sharded path.
    uses_pallas: bool = False

    def _jitted_sharded(self, layout, donate: bool = False) -> Callable:
        """The chain lowered ONCE per mesh layout with the SpecLayout
        convention's explicit shardings (rows sharded in, rows sharded
        out) — memoized per (transformer, layout, donate) like
        ``_jitted``. The donated variant aliases the staged input buffer
        into the chain's output (``SpecLayout.jit`` donation)."""
        if self.shares_program():
            program = _program(self._program_name(), layout, donate)
            return _Bound(program, self)
        cache = getattr(self, "_shard_jit_cache", None)
        if cache is None:
            cache = {}
            object.__setattr__(self, "_shard_jit_cache", cache)
        key = (layout, donate)
        fn = cache.get(key)
        if fn is None:
            body = lambda X: self.apply_sharded(X, layout)  # noqa: E731
            fn = cache[key] = _Closure(layout.jit(
                body, donate_argnums=(0,) if donate else ()
            ))
        return fn

    def _donation_eligible(self, X, layout) -> bool:
        """Can the staged input buffer alias into this chain's output?
        XLA matches donated buffers to outputs by aval (shape + dtype);
        a shrinking/growing chain has no match, so donating there would
        be a per-compile warning and a no-op — refused up front (and
        counted by the caller). Shape-only: one ``eval_shape`` per
        (shape, dtype, layout), memoized beside the jit cache."""
        from keystone_tpu.config import config

        if not config.donate_buffers:
            return False
        cache = getattr(self, "_donate_ok_cache", None)
        if cache is None:
            cache = {}
            object.__setattr__(self, "_donate_ok_cache", cache)
        key = (tuple(X.shape), str(X.dtype), layout)
        ok = cache.get(key)
        if ok is None:
            try:
                spec = jax.ShapeDtypeStruct(X.shape, X.dtype)
                out = jax.eval_shape(
                    lambda a: self.apply_sharded(a, layout), spec
                )
                leaves = jax.tree_util.tree_leaves(out)
                ok = any(
                    getattr(leaf, "shape", None) == spec.shape
                    and getattr(leaf, "dtype", None) == spec.dtype
                    for leaf in leaves
                )
            except Exception:  # lint: broad-ok abstract eval is best-effort; anything it can't trace just keeps the undonated lowering
                ok = False
            cache[key] = ok
        return ok

    def _staged_call(self, staged, layout):
        """Run the lowered chain on a staging buffer ``_sharded_call``
        itself created (``put``/``pad_put``) — the ONLY buffers the chain
        ever donates: they are provably dead here, unlike caller-owned
        arrays (anything placed upstream can be multi-consumer via
        gather/by-hash memo). Donation is refused — counted, never
        silent — when no output aval can alias the buffer."""
        from keystone_tpu.utils.metrics import sharding_counters

        donate = self._donation_eligible(staged, layout)
        if donate:
            sharding_counters.bump("buffers_donated")
        else:
            from keystone_tpu.config import config

            if config.donate_buffers:
                sharding_counters.bump("donation_refused")
        if self.uses_pallas:
            sharding_counters.bump("pallas_sharded_calls")
        return self._jitted_sharded(layout, donate=donate)(staged)

    def _sharded_call(self, X, layout):
        """Run the chain data-parallel under ``layout``: host batches are
        staged onto the mesh by this call (``put`` when divisible,
        mask-pad + trim otherwise) and the staging copy is donated into
        the lowered chain where an output can alias it; already-sharded
        device batches go straight through the explicitly-specced jit,
        never donated (the caller owns them). Row-independence makes pad
        rows inert, so outputs are bit-identical to the unsharded walk
        while the compute spans every shard. Row-coupled host chains
        (padding unsound, rows non-divisible) keep the propagation path,
        counted so the narrow run is visible."""
        from keystone_tpu.utils.metrics import sharding_counters

        n = int(X.shape[0])
        if isinstance(X, jax.Array):
            # Caller-owned placement (DatasetOperator / upstream chain):
            # only divisible row counts carry a layout here.
            sharding_counters.bump("sharded_chain_calls")
            if self.uses_pallas:
                sharding_counters.bump("pallas_sharded_calls")
            return self._jitted_sharded(layout)(X)
        if n % layout.num_shards == 0:
            sharding_counters.bump("sharded_chain_calls")
            return self._staged_call(layout.put(X), layout)
        if not self.row_independent:
            sharding_counters.bump("fallback_row_coupled")
            return self._jitted()(X)
        padded, n = layout.pad_put(X)
        sharding_counters.bump("sharded_chain_calls")
        sharding_counters.bump("batches_padded")
        sharding_counters.bump("pad_rows_added", padded.shape[0] - n)
        out = self._staged_call(padded, layout)
        return out[:n]

    def __getstate__(self):
        """Pickle without the per-instance jit caches (jitted callables are
        unpicklable; they rebuild lazily after load). Non-mutating, so
        persisting a live fitted transformer keeps its warm compilation."""
        state = dict(self.__dict__)
        for name in _JIT_CACHES:
            state.pop(name, None)
        return state

    def signature(self) -> Any:
        """Key for structural prefix hashing; object identity by default.

        Deterministic nodes either override this to build a
        ``stable_signature`` from their current parameters, or (factory-
        created nodes) install one on ``self._sig`` — then two separately-
        constructed-but-identical nodes hash (and cache) alike, including
        across pipeline rebuilds in one session. The id fallback carries the
        UNSTABLE poison so it can never masquerade as persistable content.
        """
        sig = getattr(self, "_sig", None)
        if sig is not None:
            return sig
        from keystone_tpu.workflow.fingerprint import UNSTABLE

        return ("t-id", id(self), UNSTABLE)

    def stable_signature(self, *params) -> tuple:
        """Content-based signature: concrete class + constructor params.
        The class OBJECT is part of the key (not its name), so two distinct
        classes — even same-named locals — can never collide."""
        return (type(self),) + params

    def chain_hash(self, h_in: int) -> int:
        """Prefix hash of applying this transformer to an input with hash
        ``h_in``. FusedTransformer folds so fusion never changes hashes."""
        return hash((("transformer", self.signature()), (h_in,)))

    def chain_digest(self, d_in):
        """Content-stable fold mirroring ``chain_hash`` (None = unstable)."""
        if d_in is None:
            return None
        from keystone_tpu.workflow.fingerprint import digest_tree

        return digest_tree((("transformer", self.signature()), (d_in,)))

    # -- composition sugar -------------------------------------------------

    def to_pipeline(self) -> "Pipeline":
        source = fresh_source_id()
        graph, nid = Graph().add(TransformerOperator(self), [source])
        return Pipeline(graph, source, nid)

    def and_then(self, nxt, *fit_args) -> "Pipeline":
        return self.to_pipeline().and_then(nxt, *fit_args)

    def apply_pipeline(self, data) -> "PipelineDataset":
        return self.to_pipeline().apply(data)

    def __call__(self, data):
        """Eager convenience: transform a batch (or datum) directly."""
        if isinstance(data, PipelineDataset):
            return self.to_pipeline().apply(data)
        if _is_array(data):
            return self.batch_call(data)
        return self.apply_batch(data)


class FusedTransformer(Transformer):
    """A chain of jittable transformers compiled as one XLA computation.

    Produced by the optimizer's chain-fusion rule — the analog of the
    reference's lowering of a whole RDD stage, except the "stage" here is a
    single jitted program XLA can fuse end-to-end.
    """

    # The chain's arrays are its stages', handed through as one pytree.
    array_fields = ("stages",)

    def __init__(self, stages: Sequence[Transformer]):
        flat: List[Transformer] = []
        for s in stages:
            if isinstance(s, FusedTransformer):
                flat.extend(s.stages)
            else:
                flat.append(s)
        self.stages = flat
        self.jittable = all(s.jittable for s in flat)
        self.row_independent = all(
            getattr(s, "row_independent", True) for s in flat
        )
        self.uses_pallas = self.fused_stages > 0 or any(
            getattr(s, "uses_pallas", False) for s in flat
        )

    @property
    def fused_stages(self) -> int:
        """How many of the stages run inside another's kernel or with one
        behind them (``Transformer.takes``): 3 for a convolver that took
        its rectifier and pooler, 0 for a chain walked stage by stage."""
        return sum(
            1 + len(taken) for _stage, taken in _steps(self.stages) if taken
        )

    def apply_batch(self, X):
        tiling = self.row_tiling(X)
        if tiling is None:
            return self._apply_stages(X)
        # Tile by tile inside the one program: rows padded to a whole
        # number of tiles (pad rows are inert, the stages being
        # row-independent) and trimmed again.
        tile_rows, tiles = tiling
        n = X.shape[0]
        padded = jnp.pad(
            X, ((0, tiles * tile_rows - n),) + ((0, 0),) * (X.ndim - 1)
        )
        out = jax.lax.map(
            self._apply_stages,
            padded.reshape((tiles, tile_rows) + X.shape[1:]),
        )
        return jax.tree_util.tree_map(
            lambda o: o.reshape((tiles * tile_rows,) + o.shape[2:])[:n], out
        )

    def _apply_stages(self, X):
        return _walk(self.stages, X)[-1]

    def row_tiling(self, X):
        """``(tile_rows, tiles)`` where ``apply_batch`` runs ``X`` in row
        tiles, else None: a chain of jittable, row-independent stages one
        of whose steps, over all of ``X``'s rows, would hold more than an
        eighth of the device's memory (its output, and what it lays out
        for a kernel it runs: ``Transformer.scratch_with``). From sizes the code can
        see (the stages' propagated shapes, ``device_hbm_bytes()``), at
        trace time and, for a span, on the host. Only a chain that shares
        its program is asked: its tiling is found by its structure too,
        and one that keeps the closure path is jitted as it always was."""
        if not (self.jittable and self.row_independent and len(self.stages) > 1
                and getattr(X, "ndim", 0) >= 1 and self.shares_program()):
            return None
        from keystone_tpu.utils.metrics import device_hbm_bytes

        leaves, treedef = jax.tree_util.tree_flatten(self)
        return _row_tiling(
            treedef,
            tuple(jax.ShapeDtypeStruct(np.shape(a), jnp.result_type(a))
                  for a in leaves),
            jax.ShapeDtypeStruct(X.shape, X.dtype),
            device_hbm_bytes() // _TILE_HBM_SHARE,
        )

    def apply_sharded(self, X, layout):
        """A chain of row-independent stages runs every shard's rows
        through the one-device lowering (``apply_batch`` under
        ``shard_map``: the tile rule asked of the shard's own shape, a step
        that takes the stages behind it, a kernel on the rows it is
        handed), the chain's arrays replicated beside them. A chain that
        couples rows is walked stage by stage and left to the partitioner,
        each stage told the layout."""
        if self.row_independent:
            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            return shard_map(
                lambda chain, x: chain.apply_batch(x), mesh=layout.mesh,
                in_specs=(P(), P(layout.axis)), out_specs=P(layout.axis),
                check_vma=False,
            )(self, X)
        for s in self.stages:
            with stage_scope(s):
                X = s.apply_sharded(X, layout)
        return X

    def _program_name(self):
        return "_".join(s._program_name() for s in self.stages)

    def signature(self):
        return ("fused",) + tuple(s.signature() for s in self.stages)

    def chain_hash(self, h_in: int) -> int:
        # Fold stage-by-stage so the fused node's prefix hash equals the
        # unfused chain's — fusion is hash-invariant (fit_cache keeps hitting
        # whether or not a prefix got fused in a particular graph copy).
        for s in self.stages:
            h_in = s.chain_hash(h_in)
        return h_in

    def chain_digest(self, d_in):
        for s in self.stages:
            d_in = s.chain_digest(d_in)
        return d_in

    def __repr__(self):
        return "Fused(" + " | ".join(type(s).__name__ for s in self.stages) + ")"


class GatherTransformer(Transformer):
    """Branches over one input, outputs concatenated on the feature axis:
    ``Pipeline.gather`` as a single transformer.

    The serving engine compiles ONE program per bucket, so
    ``GraphExecutor.serving_chain`` lowers a fitted gather join (the
    two-branch ImageNet featurizer) to this; XLA sees both branches and
    the concatenate in the same computation.
    """

    def __init__(self, branches: Sequence[Transformer]):
        self.branches = list(branches)
        self.jittable = all(b.jittable for b in self.branches)
        self.row_independent = all(
            getattr(b, "row_independent", True) for b in self.branches
        )
        self.uses_pallas = any(
            getattr(b, "uses_pallas", False) for b in self.branches
        )

    def apply_batch(self, X):
        return jnp.concatenate(
            [b.apply_batch(X) for b in self.branches], axis=-1
        )

    def apply_sharded(self, X, layout):
        return jnp.concatenate(
            [b.apply_sharded(X, layout) for b in self.branches], axis=-1
        )

    def signature(self):
        return ("gather",) + tuple(b.signature() for b in self.branches)

    def __repr__(self):
        return "Gather(" + ", ".join(repr(b) for b in self.branches) + ")"


class BranchStack(Transformer):
    """Gather branches that are one chain with different arrays, as one
    transformer: the chain once, each of its arrays stacked on a leading
    axis of ``branches`` (``of``), one argument of the program where the
    branches would be a closure each. ``apply_batch`` is the gather's
    output, every branch's columns side by side; ``block(X, b)`` is column
    block ``b`` alone, its ``per_block`` branches: what a solver makes one
    block at a time (``linalg.bcd.BlockSource``). A ragged last block's
    missing branches are zero columns."""

    array_fields = ("chain",)

    def __init__(self, chain: FusedTransformer, branches: int, per_block: int,
                 width: int):
        self.chain = chain
        self.branches = int(branches)
        self.per_block = int(per_block)
        self.width = int(width)

    @staticmethod
    def of(chains: Sequence[Sequence[Transformer]], block: int, x) -> Optional["BranchStack"]:
        """The branches ``chains`` (each a branch's stages, in order)
        stacked for blocks of ``block`` columns and an input like ``x`` (a
        shape and dtype), or None where they are not one chain (the same
        classes and static fields, arrays of the same shapes: one pytree
        structure), are not jittable and row-independent, or a branch's
        width does not divide ``block``."""
        fused = [FusedTransformer(c) for c in chains]

        def structure(f):
            leaves, tree = jax.tree_util.tree_flatten(f)
            return tree, [(np.shape(a), jnp.result_type(a)) for a in leaves]

        try:
            one = all(structure(f) == structure(fused[0]) for f in fused[1:])
        except (TypeError, ValueError):  # a static field that compares as an array
            return None
        if not one or not all(f.jittable and f.row_independent for f in fused):
            return None
        out = jax.eval_shape(fused[0]._apply_stages, x)
        if len(out.shape) != 2 or block % out.shape[1]:
            return None
        chain = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *fused)
        return BranchStack(chain, len(fused), block // out.shape[1], out.shape[1])

    def _columns(self, X, first, count):
        """Branches ``first`` to ``first + count`` on ``X``, their columns
        side by side as the gather lays them, one branch at a time into
        its slice of the output (what a branch lays out in between is
        held for one branch, not for all); past the last branch, zeros."""
        w = self.width

        def branch(i, out):
            at = first + i
            chain = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(
                    a, jnp.minimum(at, self.branches - 1), keepdims=False),
                self.chain)
            y = chain._apply_stages(X)
            if self.branches % self.per_block:
                y = jnp.where(at < self.branches, y, 0)
            return jax.lax.dynamic_update_slice_in_dim(out, y.astype(out.dtype), i * w, 1)

        out = jnp.zeros((X.shape[0], count * w), X.dtype)
        return jax.lax.fori_loop(0, count, branch, out)

    def apply_batch(self, X):
        return self._columns(X, 0, self.branches)

    def block(self, X, b):
        return self._columns(X, b * self.per_block, self.per_block)


# ---------------------------------------------------------------------------
# Estimators
# ---------------------------------------------------------------------------


def _splice_data(graph: Graph, data: Any):
    """Splice data (raw batch or lazy PipelineDataset) into ``graph``.

    Returns (graph, graph_id_producing_the_data).
    """
    if isinstance(data, PipelineDataset):
        return graph.union(data.graph), data.sink
    g, nid = graph.add(DatasetOperator(data), [])
    return g, nid


def _estimator_signature(est) -> tuple:
    """Content signature: class + public hyperparameter fields.

    Fields starting with ``_`` and names in ``_signature_exclude`` (mutable
    outputs like diagnostics set at fit time) are skipped. Values without a
    content identity poison the tree — the in-process cache still works via
    their ids, but nothing gets persisted under an unstable key.
    """
    from keystone_tpu.workflow.fingerprint import stable_value

    exclude = set(getattr(est, "_signature_exclude", ()))
    fields = {
        k: v
        for k, v in est.__dict__.items()
        if not k.startswith("_") and k not in exclude
    }
    return ("est", stable_value(type(est)), stable_value(fields))


class Estimator:
    """``fit(data) -> Transformer``. Ref: workflow/Estimator.scala [unverified]."""

    def signature(self) -> tuple:
        return _estimator_signature(self)

    def fit(self, data) -> Transformer:
        raise NotImplementedError

    def with_data(self, data) -> "Pipeline":
        """A pipeline that lazily fits this estimator on ``data`` and applies
        the fitted transformer to the pipeline input (Estimator.withData)."""
        graph = Graph()
        graph, data_id = _splice_data(graph, data)
        graph, est_id = graph.add(EstimatorOperator(self), [data_id])
        source = fresh_source_id()
        graph, out_id = graph.add(DelegatingOperator(), [est_id, source])
        return Pipeline(graph, source, out_id)

    def fit_pipeline(self, data) -> "Pipeline":
        """Eagerly fit and return the fitted transformer as a pipeline."""
        return self.fit(_force(data)).to_pipeline()


class LabelEstimator:
    """``fit(data, labels) -> Transformer``.

    Ref: workflow/LabelEstimator.scala [unverified].
    """

    def signature(self) -> tuple:
        return _estimator_signature(self)

    def fit(self, data, labels) -> Transformer:
        raise NotImplementedError

    def with_data(self, data, labels) -> "Pipeline":
        graph = Graph()
        graph, data_id = _splice_data(graph, data)
        graph, labels_id = _splice_data(graph, labels)
        graph, est_id = graph.add(EstimatorOperator(self), [data_id, labels_id])
        source = fresh_source_id()
        graph, out_id = graph.add(DelegatingOperator(), [est_id, source])
        return Pipeline(graph, source, out_id)


# ---------------------------------------------------------------------------
# Pipeline
# ---------------------------------------------------------------------------


class Pipeline:
    """A lazily-constructed dataflow from one source to one sink.

    Ref: workflow/Pipeline.scala [unverified].
    """

    def __init__(self, graph: Graph, source: SourceId, sink: GraphId):
        self.graph = graph
        self.source = source
        self.sink = sink

    # -- composition -------------------------------------------------------

    @staticmethod
    def _coerce(obj) -> "Pipeline":
        if isinstance(obj, Pipeline):
            return obj
        if isinstance(obj, Transformer):
            return obj.to_pipeline()
        raise TypeError(f"cannot compose with {type(obj).__name__}")

    def and_then(self, nxt, *fit_args) -> "Pipeline":
        """``pipeline.and_then(transformer_or_pipeline)``, or
        ``pipeline.and_then(estimator, data[, labels])`` which fits the
        estimator on this pipeline applied to ``data``."""
        if isinstance(nxt, (Estimator, LabelEstimator)):
            return self._and_then_fit(nxt, *fit_args)
        if fit_args:
            raise TypeError("fit data only valid when composing an estimator")
        nxt = Pipeline._coerce(nxt)
        merged = self.graph.union(nxt.graph)
        merged, (new_sink,) = merged.instantiate([nxt.sink], {nxt.source: self.sink})
        return Pipeline(merged.pruned([new_sink]), self.source, new_sink)

    def _and_then_fit(self, est, data, labels=None) -> "Pipeline":
        if labels is None and not isinstance(est, Estimator):
            raise TypeError("LabelEstimator requires labels")
        if labels is not None and not isinstance(est, LabelEstimator):
            raise TypeError("labels are only valid for a LabelEstimator")
        features = self.apply(data)
        if labels is None:
            tail = est.with_data(features)
        else:
            tail = est.with_data(features, labels)
        return self.and_then(tail)

    @staticmethod
    def gather(branches: Sequence[Union["Pipeline", Transformer]]) -> "Pipeline":
        """Merge parallel branches over the same input by concatenating their
        outputs on the feature axis (Pipeline.gather)."""
        branches = [Pipeline._coerce(b) for b in branches]
        source = fresh_source_id()
        merged = Graph()
        sinks: List[GraphId] = []
        for b in branches:
            merged = merged.union(b.graph)
            merged, (s,) = merged.instantiate([b.sink], {b.source: source})
            sinks.append(s)
        merged, out = merged.add(GatherOperator(), sinks)
        return Pipeline(merged.pruned([out]), source, out)

    def cache(self) -> "Pipeline":
        """Mark this pipeline's output for session-cache persistence (the
        explicit Cacher; the auto-cache rule inserts these automatically)."""
        from keystone_tpu.workflow.cache import CacheOperator

        graph, nid = self.graph.add(CacheOperator(), [self.sink])
        return Pipeline(graph, self.source, nid)

    # -- application -------------------------------------------------------

    def apply(self, data) -> "PipelineDataset":
        """Lazily apply to a batch (array / host sequence / PipelineDataset)."""
        if isinstance(data, PipelineDataset):
            merged = self.graph.union(data.graph)
            merged, (sink,) = merged.instantiate([self.sink], {self.source: data.sink})
            return PipelineDataset(merged.pruned([sink]), sink)
        graph, data_id = self.graph.add(DatasetOperator(data), [])
        graph, (sink,) = graph.instantiate([self.sink], {self.source: data_id})
        return PipelineDataset(graph.pruned([sink]), sink)

    def __call__(self, data) -> "PipelineDataset":
        return self.apply(data)

    def apply_batches(
        self, batches, prefetch_depth: Optional[int] = None, engine=None
    ):
        """Stream row batches through the pipeline with ingest overlap.

        ``batches`` is any iterable of ``(features, labels-or-None)`` pairs
        or bare feature batches (``loaders.stream.BatchIterator`` included).
        The upstream producer — CSV parse, JPEG decode, ``map_batches``
        featurization — runs on a background prefetch thread
        (``prefetch_depth`` deep, default ``config.prefetch_depth``; 0 =
        synchronous passthrough) while the fused transformer chain computes
        on the current batch, so host ingest leaves the device's critical
        path. Yields ``(transformed_batch, labels)`` in source order —
        the out-of-core scoring/featurization loop of the streamed
        pipelines.

        ``engine`` takes a ``workflow.serving.CompiledPipeline`` (e.g.
        ``self.compiled()``) and round-robins batches over its device
        replica pool instead of executing the graph per batch: up to
        in-flight × replicas device calls overlap with the prefetcher —
        the data-parallel offline apply. Requires the serve chain to be
        linear, jittable, and row-independent (``compiled()`` enforces
        this); outputs are the padded-bucket executables' and so can
        differ from graph execution in the last ulp across gemm shapes.
        """
        from contextlib import nullcontext

        from keystone_tpu.loaders.stream import prefetched
        from keystone_tpu.utils.metrics import active_tracer

        if engine is not None:
            yield from engine.apply_batches(batches, prefetch_depth)
            return

        tracer = active_tracer()  # once per stream, like the fault plan
        with prefetched(iter(batches), prefetch_depth) as src:
            for i, item in enumerate(src):
                if isinstance(item, tuple) and len(item) == 2:
                    X, y = item
                else:
                    X, y = item, None
                ctx = (
                    tracer.span(
                        "pipeline.apply_batch", "pipeline", batch=i,
                        rows=int(getattr(X, "shape", (len(X),))[0]),
                    )
                    if tracer is not None else nullcontext()
                )
                with ctx:
                    out = self.apply(X).get()
                yield out, y

    def apply_datum(self, datum) -> Any:
        """Apply to a single datum, eagerly (driver-local in the reference).

        Lifts the datum to a one-element batch so every transformer sees the
        leading example axis its ``apply_batch`` contract promises, then
        unwraps the result.
        """
        if _is_array(datum) or jnp.isscalar(datum):
            batch: Any = jnp.asarray(datum)[None, ...]
        else:
            batch = [datum]
        from keystone_tpu.workflow.executor import PipelineEnv

        ds = self.apply(batch)
        fitted_graph = PipelineEnv.get().executor.fit_estimators(ds.graph, ds.sink)
        out = PipelineEnv.get().execute(fitted_graph, ds.sink)
        return out[0]

    # -- fitting -----------------------------------------------------------

    def fit(self, profile: Optional[bool] = None) -> "Pipeline":
        """Force every estimator in the graph and return a transformer-only
        pipeline (the reference's fitted pipeline).

        ``profile=True`` forces per-node resource attribution for this
        fit (``utils.metrics.profile_scope``) regardless of
        KEYSTONE_PROFILE, and logs the attribution table — wall/device
        time, cost-model FLOPs/bytes, output nbytes, HBM delta per node
        — when the fit completes; the rows stay readable afterwards via
        ``utils.metrics.resource_profile`` and the registry/Prometheus
        surface, AND this fit's own delta is attached to the returned
        pipeline as ``fit_profile`` (a ``profile_store.FitProfile``) so
        callers can inspect or persist it without re-reading the
        process-wide registry. When a profile store is configured
        (``KEYSTONE_PROFILE_STORE`` / ``config.profile_store``) the
        measurements are saved there automatically, keyed by the
        pipeline's content digest — the profile-once half of the
        profile-guided optimizer loop. ``None`` (default) follows
        ``config.profile``. Profiling never changes fit OUTPUTS
        (bit-identical either way); it only measures.

        Ref: Pipeline.fit returning FittedPipeline [unverified].
        """
        from contextlib import nullcontext

        from keystone_tpu.utils.metrics import (
            active_tracer,
            profile_scope,
            resource_profile,
        )
        from keystone_tpu.workflow.analysis import enforce_lint
        from keystone_tpu.workflow.executor import PipelineEnv

        # Opt-in static gate (KEYSTONE_LINT=warn|error, default off):
        # graph hazards surface before any estimator runs.
        enforce_lint(self, "fit")
        # Cold path (once per fit): nullcontext keeps one call body; the
        # hot loops (solvers, prefetch, serving) branch explicitly instead.
        tracer = active_tracer()
        # mark() scopes the logged table to THIS fit's delta — the
        # process-wide profile keeps accumulating for registry readers.
        mark = resource_profile.mark() if profile else None
        dmark = resource_profile.mark_digests() if profile else None
        with (profile_scope() if profile else nullcontext()):
            with (tracer.span("pipeline.fit", "pipeline")
                  if tracer is not None else nullcontext()):
                graph = PipelineEnv.get().executor.fit_estimators(
                    self.graph, self.sink
                )
        fitted = Pipeline(graph, self.source, self.sink)
        if profile:
            import logging

            logging.getLogger("keystone_tpu").info(
                "fit attribution:\n%s", resource_profile.table(since=mark)
            )
            fitted.fit_profile = self._build_fit_profile(mark, dmark)
        # Prune to the subgraph feeding our sink.
        return fitted

    def _build_fit_profile(self, mark, dmark):
        """This fit's measurement handle (+ auto-save when a store is
        configured and the pipeline has content identity)."""
        from keystone_tpu.config import resolved_profile_store
        from keystone_tpu.utils.metrics import (
            resource_profile,
            runtime_fingerprint,
        )
        from keystone_tpu.workflow.profile_store import (
            FitProfile,
            ProfileStoreError,
            pipeline_profile_digest,
        )

        fp = FitProfile(
            pipeline_digest=pipeline_profile_digest(self.graph, self.sink),
            fingerprint=runtime_fingerprint(),
            rows=resource_profile.rows(since=mark),
            digests=resource_profile.digest_rows(since=dmark),
        )
        if (
            resolved_profile_store()
            and fp.pipeline_digest is not None
            and fp.digests
            # An empty delta (warm session: every node served from the
            # fit cache) must KEEP the existing store entry, not clobber
            # a good one with zero rows — the _profile_save_ctx rule.
        ):
            import logging

            try:
                fp.save()
                logging.getLogger("keystone_tpu").info(
                    "measured profile saved: %s", fp.saved_to
                )
            except ProfileStoreError as e:
                logging.getLogger("keystone_tpu").warning(
                    "measured profile not saved: %s", e
                )
        return fp

    def refit_stream(self, batches, every: int = 1, *, decay=None,
                     window=None, state=None, seed_state: bool = True):
        """Incrementally refit the HEAD of this pipeline on a labeled
        stream, freezing the fitted featurize stages.

        ``self`` must be the ``featurize.and_then(head_est, X0, y0)``
        shape (sink = a lazily-fit estimator application). The pipeline
        is fitted once up front — every featurize stage (including
        estimator-fitted ones like feature selectors) is FROZEN from
        then on. Each ``(X, y)`` batch from ``batches`` is featurized
        through the frozen prefix and folded into the head's retained
        accumulators (``head_est.partial_fit``); every ``every`` batches
        the head is re-solved cheaply and a refreshed fitted pipeline is
        yielded (prefix reused by reference — zero featurize refit cost).
        A final refresh is yielded for any tail batches.

        ``seed_state=True`` (default) folds the INITIAL training problem
        into a fresh state first, so the first tick re-solves
        initial ∪ streamed rather than the first batches alone; pass a
        ``state`` (or ``seed_state=False``) to refit on the stream only.

        A head WITHOUT ``partial_fit`` still works but silently costs a
        FULL head refit per cadence tick (all streamed features are
        buffered): the fallback is logged once and counted
        (``online.full_refits``), and the static linter flags the shape
        up front (KG105 via ``Pipeline.lint(refit=True)``). The
        ``decay``/``window`` forgetting modes need the online path and
        are REFUSED (not silently dropped) on the fallback.

        ``decay``/``window`` select the forgetting mode (see
        ``workflow/online.py``); ``state`` lets a caller hand in (and
        keep observing) the retained ``OnlineState``.

        Validation, the lint gate, the initial fit, and the seed all run
        EAGERLY (this returns an inner generator): a misconfiguration
        refuses HERE, not at whatever distant point first iterates.
        """
        import numpy as np

        from keystone_tpu.utils.metrics import online_counters
        from keystone_tpu.workflow.analysis import enforce_lint
        from keystone_tpu.workflow.online import (
            refit_head_estimator,
            split_fitted_head,
            supports_partial_fit,
        )

        # Opt-in static gate (KEYSTONE_LINT): KG105 names the
        # full-refit-per-tick hazard before any batch streams.
        enforce_lint(self, "refit_stream", refit=True)
        head_est = refit_head_estimator(self.graph, self.sink)
        if head_est is None:
            raise ValueError(
                "refit_stream needs a pipeline whose sink is a lazily-fit "
                "estimator head (featurize.and_then(est, data, labels))"
            )
        fitted = self.fit()
        prefix, _ = split_fitted_head(fitted)  # ticks rebuild the head
        online = supports_partial_fit(head_est)
        if not online:
            import logging

            if decay is not None or window is not None:
                # Refuse, never silently drop: the fallback's unweighted
                # full refit is NOT the forgetting semantics asked for.
                raise ValueError(
                    f"decay/window need a partial_fit head; "
                    f"{type(head_est).__name__} would full-refit with "
                    "every batch weighted equally"
                )
            if state is not None:
                # Same rule for a caller-supplied state: the fallback
                # never reads it, and silently excluding its retained
                # history from every tick is a wrong model, not a mode.
                raise ValueError(
                    f"a caller-supplied OnlineState needs a partial_fit "
                    f"head; {type(head_est).__name__}'s full-refit "
                    "fallback would never fold it"
                )
            logging.getLogger("keystone_tpu").warning(
                "refit_stream: %s lacks partial_fit — every cadence tick "
                "is a FULL head refit over the buffered stream (KG105)",
                type(head_est).__name__,
            )
        feats_all: List[Any] = []
        ys_all: List[Any] = []
        if state is None and seed_state:
            from keystone_tpu.workflow.online import head_fit_values

            feats0, labels0 = head_fit_values(self.graph, self.sink)
            if online:
                state = head_est.partial_fit(feats0, labels0,
                                             window=window)
            else:
                # The fallback honors the seed too: its full refits run
                # over initial ∪ streamed, same as the online path.
                feats_all.append(feats0)
                ys_all.append(labels0)

        def tick():
            from keystone_tpu.workflow.online import combine_head

            if online:
                new_head = head_est.solve_online(state)
            else:
                online_counters.bump("full_refits")
                new_head = head_est.fit(
                    np.concatenate([np.asarray(f) for f in feats_all]),
                    np.concatenate([np.asarray(y) for y in ys_all]),
                )
            return combine_head(prefix, new_head)

        def run():
            nonlocal state
            since = 0
            for item in batches:
                if not (isinstance(item, tuple) and len(item) == 2):
                    raise ValueError(
                        "refit_stream needs (features, labels) batches"
                    )
                X, y = item
                feats = prefix.apply(X).get() if prefix is not None else X
                if online:
                    state = head_est.partial_fit(
                        feats, y, state=state, decay=decay, window=window
                    )
                else:
                    # NOT batches_folded: nothing reached retained
                    # accumulators on this path — the buffer feeds the
                    # counted full refit. Copies, same as
                    # OnlineState.fold: a caller reusing one
                    # preallocated batch buffer must not overwrite what
                    # a later tick will refit on.
                    feats_all.append(np.array(feats, copy=True))
                    ys_all.append(np.array(y, copy=True))
                    online_counters.bump("batches_buffered")
                since += 1
                if since >= int(every):
                    since = 0
                    yield tick()
            if since > 0:
                yield tick()

        return run()

    def compiled(
        self, buckets=None, max_batch=None, donate=None, devices=None,
        inflight=None,
    ):
        """Fit (if needed) and lower to a shape-stable serving engine.

        Returns a ``workflow.serving.CompiledPipeline``: call ``warmup()``
        with the traffic's feature shape to AOT-compile the whole bucket
        ladder — on every device of the replica pool (``devices=``, env
        ``KEYSTONE_SERVE_DEVICES``, default all local) — before first
        traffic, then serve mixed-size batches with zero steady-state
        recompiles. Requires the serve path to be jittable,
        row-independent transformers (chains, and gather joins of them).
        """
        from keystone_tpu.workflow.analysis import enforce_lint
        from keystone_tpu.workflow.serving import CompiledPipeline

        # Opt-in static gate: with KEYSTONE_LINT=error a chain the engine
        # would refuse (host/row-coupled nodes) fails HERE, with a
        # rule id and fix hint, before fit or warmup spend any compute.
        # The engine always has a bucket ladder, so KG101 is moot.
        enforce_lint(self, "compiled", serve=True, have_ladder=True)
        return CompiledPipeline(
            self, buckets=buckets, max_batch=max_batch, donate=donate,
            devices=devices, inflight=inflight,
        )

    # -- introspection -----------------------------------------------------

    def lint(self, example=None, serve: bool = False,
             have_ladder=None, refit: bool = False) -> "LintReport":
        """Statically lint the pipeline DAG (workflow/analysis.py): the
        abstract shape/dtype pass plus the KG rule catalog. ``example``
        (sample batch, ShapeDtypeStruct, or per-row feature-shape tuple)
        feeds shape propagation; ``serve=True`` escalates serveability
        findings to errors — the would-be ``compiled()`` contract;
        ``refit=True`` checks the ``refit_stream`` contract (KG105).
        Returns a ``LintReport``; never executes the graph."""
        from keystone_tpu.workflow.analysis import lint_graph

        return lint_graph(
            self.graph, self.source, self.sink,
            example=example, serve=serve, have_ladder=have_ladder,
            refit=refit,
        )

    def transformers(self) -> List[Transformer]:
        """Transformer chain in topological order (fitted pipelines only)."""
        out = []
        for nid in self.graph.reachable([self.sink]):
            op = self.graph.operators[nid]
            if isinstance(op, TransformerOperator):
                out.append(op.transformer)
        return out

    def describe(self) -> str:
        lines = []
        for nid in self.graph.reachable([self.sink]):
            op = self.graph.operators[nid]
            deps = ", ".join(map(repr, self.graph.dependencies[nid]))
            lines.append(f"{nid!r}: {op.label()} <- [{deps}]")
        return "\n".join(lines)

    def to_dot(self) -> str:
        """Graphviz DOT source of the DAG — the pipeline-debugging export
        (Ref: workflow Pipeline DOT export [unverified, low confidence]).
        Render with ``dot -Tpng``; sources are diamonds, the sink is bold.
        """
        lines = ["digraph pipeline {", "  rankdir=LR;"]
        seen_srcs = set()
        for nid in self.graph.reachable([self.sink]):
            op = self.graph.operators[nid]
            style = ' style=bold' if nid == self.sink else ""
            lines.append(f'  "{nid!r}" [label="{op.label()}"{style}];')
            for dep in self.graph.dependencies[nid]:
                if isinstance(dep, SourceId) and dep not in seen_srcs:
                    seen_srcs.add(dep)
                    lines.append(f'  "{dep!r}" [label="input" shape=diamond];')
                lines.append(f'  "{dep!r}" -> "{nid!r}";')
        lines.append("}")
        return "\n".join(lines)


class PipelineDataset:
    """Lazy handle to the result of applying a pipeline to a batch.

    Ref: workflow/PipelineDataset.scala [unverified]. ``get()`` triggers
    optimization + execution (memoized).
    """

    def __init__(self, graph: Graph, sink: GraphId):
        self.graph = graph
        self.sink = sink
        self._value: Any = None
        self._computed = False

    def get(self) -> Any:
        if not self._computed:
            from contextlib import nullcontext

            from keystone_tpu.utils.metrics import active_tracer
            from keystone_tpu.workflow.executor import PipelineEnv

            tracer = active_tracer()
            ctx = (
                tracer.span("pipeline.apply", "pipeline")
                if tracer is not None else nullcontext({})
            )
            with ctx as attrs:
                self._value = PipelineEnv.get().optimize_and_execute(
                    self.graph, self.sink
                )
                shape = getattr(self._value, "shape", None)
                if shape is not None:
                    attrs["shape"] = [int(s) for s in shape]
            self._computed = True
        return self._value


def _force(data):
    return data.get() if isinstance(data, PipelineDataset) else data
