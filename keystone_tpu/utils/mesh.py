"""Device-mesh helpers and the data-parallel sharding convention.

The Spark execution substrate of the reference (RDD partitions over executors,
Ref: workflow over org.apache.spark.rdd.RDD [unverified]) maps here to a
``jax.sharding.Mesh`` over TPU chips: the ``data`` axis plays the role of RDD
row partitioning, and collectives over ICI replace ``treeAggregate``/shuffle.

``SpecLayout`` is the one sharding convention the workflow layer threads
through fused featurize chains (arXiv:2112.09017's spec-threading for
gram-accumulation-as-all-reduce designs): activations row-sharded on
``config.data_axis``, params and small outputs replicated. A fused chain
lowers ONCE under ``jax.jit`` with these explicit ``in_shardings`` /
``out_shardings`` instead of inheriting whatever placement its input
happened to carry — input placement can no longer silently degrade a
chain to single-device.

Everything in keystone_tpu is written to be mesh-shape agnostic: the same code
runs on 1 chip, on N fake CPU devices (tests), and on a pod slice.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from keystone_tpu.config import config

_default_mesh: Optional[Mesh] = None


class MeshMismatchError(RuntimeError):
    """Persisted solver/checkpoint state was recorded under a different
    mesh width (device count / data axis) than the one resuming it, and
    could not be migrated.

    Raised — never silently resumed and never silently restarted — by the
    streaming solvers' checkpoint binding when elastic migration is
    pinned off (``KEYSTONE_ELASTIC_MESH=0``) or the state is genuinely
    non-migratable (a torn/partial per-shard payload): continuing
    differently-folded state unexamined would hand the operator a
    'resumed' solve whose provenance lies about the mesh it ran on.
    Recovery: ``utils.mesh.reshard_state`` migrates the state onto the
    current width (the default-on ``KEYSTONE_ELASTIC_MESH`` path does
    this automatically at resume, counted in the "elastic" metrics
    family), or re-run on the recording mesh width. The work in the
    checkpoint is recoverable — deleting it is a last resort, not the
    advice."""


def default_mesh(devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """A 1-D mesh over all local devices on the ``data`` axis.

    Replaces the SparkContext/executor topology of the reference. Multi-host
    meshes are created the same way after ``jax.distributed.initialize`` —
    ``jax.devices()`` then spans hosts and the collectives ride ICI/DCN.
    """
    global _default_mesh
    if devices is None:
        if _default_mesh is None:
            _default_mesh = Mesh(
                np.asarray(jax.devices()), axis_names=(config.data_axis,)
            )
        return _default_mesh
    # An explicit device list is a one-off mesh; never install it as default.
    return Mesh(np.asarray(devices), axis_names=(config.data_axis,))


def set_default_mesh(mesh: Mesh) -> None:
    global _default_mesh
    _default_mesh = mesh


def reset_default_mesh() -> None:
    """Drop the memoized default mesh (the ``reset_memory_probe``
    convention): tests that fake device counts or install one-off meshes
    via ``set_default_mesh`` call this so a memoized narrow mesh can never
    leak into a later test expecting the full device set."""
    global _default_mesh
    _default_mesh = None


def data_sharding(mesh: Optional[Mesh] = None) -> NamedSharding:
    """Rows sharded over the data axis — the RDD-partitioning analog."""
    mesh = mesh or default_mesh()
    return NamedSharding(mesh, P(config.data_axis))


def replicated_sharding(mesh: Optional[Mesh] = None) -> NamedSharding:
    """Fully replicated — the Spark ``broadcast`` analog."""
    mesh = mesh or default_mesh()
    return NamedSharding(mesh, P())


def num_data_shards(mesh: Optional[Mesh] = None) -> int:
    mesh = mesh or default_mesh()
    return mesh.shape[config.data_axis]


def fold_blocks(width: int) -> int:
    """Canonical block count for the width-independent solver row fold,
    or 0 when this mesh width must fall back to the plain psum fold.

    Row reductions folded over ``config.gram_fold_blocks`` fixed row
    blocks in a balanced-tree order produce the SAME bits on any mesh
    width that divides the block count — the property that lets a solve
    checkpointed on one width resume on another bit-identically (the
    elastic mesh contract). Active only when both the block count and
    the width are powers of two with ``width <= blocks``."""
    blocks = int(config.gram_fold_blocks or 0)
    if blocks <= 0 or blocks & (blocks - 1):
        return 0
    width = int(width)
    if width <= 0 or width & (width - 1) or blocks % width:
        return 0
    return blocks


def pad_multiple(width: int) -> int:
    """The row-padding multiple for solver operands on a ``width``-shard
    mesh: the canonical fold block count when the deterministic fold is
    active (every width's rows then pad identically, which is what keeps
    the fold's block boundaries — and therefore its bits —
    width-independent), else the mesh width."""
    return fold_blocks(width) or int(width)


def pad_rows(x: np.ndarray | jax.Array, multiple: int):
    """Pad the leading axis to a multiple, returning (padded, n_real).

    Zero rows are harmless for gram/normal-equation reductions and are masked
    out by consumers that care (e.g. evaluators).
    """
    n = x.shape[0]
    rem = (-n) % multiple
    if rem == 0:
        return x, n
    pad_widths = [(0, rem)] + [(0, 0)] * (x.ndim - 1)
    if isinstance(x, np.ndarray):
        return np.pad(x, pad_widths), n
    import jax.numpy as jnp

    return jnp.pad(x, pad_widths), n


@dataclass(frozen=True)
class SpecLayout:
    """THE data-parallel sharding convention for fused featurize chains:
    row-sharded activations on ``axis`` (``config.data_axis``), replicated
    params/outputs — the SpecLayout-style spec threading of SNIPPETS [2].

    Hashable (frozen, Mesh is hashable), so transformers key their
    sharded-jit caches on the layout itself: one compiled executable per
    (chain, mesh) pair, lowered once with explicit shardings.
    """

    mesh: Mesh
    axis: str

    @classmethod
    def for_mesh(cls, mesh: Optional[Mesh] = None) -> "SpecLayout":
        return cls(mesh or default_mesh(), config.data_axis)

    @property
    def num_shards(self) -> int:
        return self.mesh.shape[self.axis]

    def data(self) -> NamedSharding:
        """Row-sharded: batches/activations flowing through the chain."""
        return NamedSharding(self.mesh, P(self.axis))

    def replicated(self) -> NamedSharding:
        """Replicated: fitted params, grams, solved weights."""
        return NamedSharding(self.mesh, P())

    def jit(self, fn, donate_argnums=(), params: bool = False, **jit_kwargs):
        """Lower ``fn`` (batch -> batch, row-independent) ONCE with the
        convention's explicit shardings: rows sharded in, rows sharded
        out. The explicit specs — not input inheritance — are what make
        the chain's placement a contract instead of an accident. With
        ``params``, ``fn`` is (arrays, batch) -> batch and the arrays (any
        pytree) are replicated.

        ``donate_argnums`` is honored only under ``config.donate_buffers``
        (KEYSTONE_DONATE_BUFFERS=0 pins it off) and is the caller's claim
        that those buffers are dead after the call — donate ONLY staging
        copies the caller itself created, never arrays it was handed:
        a donated buffer is deleted, and any later read raises jax's
        deleted-buffer RuntimeError. Unlike the solver loops'
        ``row_matrix.donate_argnums``, this does not refuse CPU meshes:
        the current runtime honors donation there too, which is what lets
        the fake-device tests pin deletion and aliasing for real."""
        if donate_argnums and config.donate_buffers:
            jit_kwargs["donate_argnums"] = donate_argnums
        rows = self.data()
        return jax.jit(
            fn, in_shardings=(self.replicated(), rows) if params else rows,
            out_shardings=rows, **jit_kwargs,
        )

    def put(self, x) -> jax.Array:
        """Row-shard a (divisible) batch over the mesh."""
        return jax.device_put(x, self.data())

    def pad_put(self, x):
        """Mask-pad a batch's rows to the shard multiple and shard it;
        returns (sharded_padded, n_real). Pad rows are zeros — inert for
        the row-independent chains this layout lowers, and trimmed back to
        ``n_real`` by the caller after the chain runs."""
        padded, n = pad_rows(x, self.num_shards)
        return self.put(padded), n


def layout_of_array(x) -> Optional[SpecLayout]:
    """The SpecLayout an array already carries: a ``jax.Array`` whose
    sharding is a NamedSharding row-partitioned on a >1-shard data axis
    (the placement ``DatasetOperator`` gives divisible batches). None for
    host arrays, replicated/single-device arrays, and foreign layouts."""
    if not isinstance(x, jax.Array):
        return None
    sharding = getattr(x, "sharding", None)
    if not isinstance(sharding, NamedSharding):
        return None
    mesh = sharding.mesh
    axis = config.data_axis
    if axis not in mesh.axis_names or mesh.shape[axis] <= 1:
        return None
    spec = sharding.spec
    if not spec or spec[0] != axis:
        return None
    return SpecLayout(mesh, axis)


def is_numeric_host_batch(data) -> bool:
    """A numeric host array with rows: the only thing the graph's entry
    ever places on a device."""
    return (
        isinstance(data, np.ndarray)
        and data.ndim >= 1
        and data.dtype.kind in "biufc"
    )


def host_batch_shard_class(data, shards: Optional[int] = None) -> str:
    """THE shardability classifier for a host batch entering the graph —
    one definition shared by the runtime placement (DatasetOperator), the
    fused-chain lowering decision (``batch_layout``), and the static lint
    (KG103), so the three can never drift apart:

    - ``"inert"`` — not a numeric host array, or a 1-share mesh: nothing
      to decide;
    - ``"small"`` — below ``config.shard_min_rows``: the single-device
      fallback class (counted, never silent);
    - ``"pad"`` — rows never divide the mesh: the mask-pad class;
    - ``"shard"`` — rows divide the mesh: direct row-sharded placement.
    """
    if not is_numeric_host_batch(data):
        return "inert"
    if shards is None:
        try:
            shards = num_data_shards()
        except RuntimeError:  # deviceless backend: no mesh to shard over
            return "inert"
    if shards <= 1:
        return "inert"
    if data.shape[0] < config.shard_min_rows:
        return "small"
    return "pad" if data.shape[0] % shards else "shard"


#: Fingerprint keys that name the MESH a solve ran on, not the problem.
MESH_FP_KEYS = ("device_count", "data_axis")


def refuse_mesh_mismatch(
    saved_fp,
    expected_fp,
    where: str,
    extra_mesh_keys: tuple = (),
    same_problem=None,
) -> bool:
    """The one mesh-width rule shared by every checkpointing solver, so
    the contract can never fork per solver: when a persisted fingerprint
    names the SAME problem as ``expected_fp`` under a DIFFERENT mesh,
    either signal the elastic migration path (``config.elastic_mesh``,
    default on — returns True, the caller migrates via ``reshard_state``)
    or raise the typed ``MeshMismatchError`` (elastic pinned off).
    Returns False when there is no same-problem mesh conflict.

    ``extra_mesh_keys`` names additional keys that legitimately follow
    the mesh (e.g. padded row counts); ``same_problem`` overrides the
    problem-identity comparison (default: dict equality) for solvers with
    tolerant float matching. Pre-manifest fingerprints (mesh keys absent
    or None) never refuse — they have no mesh claim to contradict — and
    any OTHER disagreement is the caller's warn-and-start-fresh path.
    """
    if not isinstance(saved_fp, dict):
        return False
    saved_mesh = {k: saved_fp.get(k) for k in MESH_FP_KEYS}
    if None in saved_mesh.values():
        return False
    expected_mesh = {k: expected_fp.get(k) for k in MESH_FP_KEYS}
    if saved_mesh == expected_mesh:
        return False
    excluded = set(MESH_FP_KEYS) | set(extra_mesh_keys)
    if same_problem is None:
        same_problem = lambda a, b: a == b  # noqa: E731
    if not same_problem(
        {k: v for k, v in saved_fp.items() if k not in excluded},
        {k: v for k, v in expected_fp.items() if k not in excluded},
    ):
        return False
    if config.elastic_mesh:
        return True
    raise MeshMismatchError(
        f"{where}: checkpoint was written under mesh {saved_mesh}, "
        f"but this solve runs under {expected_mesh}; elastic migration "
        "is pinned off (KEYSTONE_ELASTIC_MESH=0), so resuming solver "
        "state across the width change is refused. Recover with "
        "utils.mesh.reshard_state (or unpin KEYSTONE_ELASTIC_MESH to "
        "migrate automatically at resume), or re-run on the recording "
        "mesh width — the checkpointed work is recoverable."
    )


def mesh_resume_decision(
    saved_fp,
    expected_fp,
    where: str,
    extra_mesh_keys: tuple = (),
    same_problem=None,
):
    """THE checkpoint-resume triage every durable-state family routes
    through (stream solve, BCD, ``OnlineState``) — legacy-wildcard
    backfill, problem-identity comparison, and the mesh-width rule in one
    place, so the three can never drift apart.

    Returns ``(decision, saved_fp)`` where ``saved_fp`` has absent
    pre-manifest mesh keys backfilled (``mesh_fp_compat``) and
    ``decision`` is one of:

    - ``"resume"`` — same problem, same mesh: continue the state as-is;
    - ``"migrate"`` — same problem under a different mesh width with
      ``config.elastic_mesh`` on: the caller migrates the payload via
      ``reshard_state`` and then resumes;
    - ``"fresh"`` — a different problem (or no usable fingerprint): the
      caller's warn-and-start-fresh path.

    Raises ``MeshMismatchError`` for the same-problem/different-mesh
    case when elastic migration is pinned off.
    """
    saved_fp = mesh_fp_compat(saved_fp, expected_fp)
    if not isinstance(saved_fp, dict):
        return "fresh", saved_fp
    matches = same_problem if same_problem is not None else (
        lambda a, b: a == b
    )
    if matches(saved_fp, expected_fp):
        return "resume", saved_fp
    if refuse_mesh_mismatch(
        saved_fp, expected_fp, where,
        extra_mesh_keys=extra_mesh_keys, same_problem=same_problem,
    ):
        return "migrate", saved_fp
    return "fresh", saved_fp


def mesh_fp_compat(saved_fp, expected_fp):
    """Backfill ABSENT mesh-manifest keys in a pre-manifest fingerprint
    from the expected one (wildcards), so a legacy checkpoint of the same
    problem on the same mesh still RESUMES after the manifest upgrade
    instead of silently restarting. Keys that are present always keep
    their saved values — a real mismatch still mismatches."""
    if not isinstance(saved_fp, dict):
        return saved_fp
    out = dict(saved_fp)
    for k in MESH_FP_KEYS:
        if k not in out and k in expected_fp:
            out[k] = expected_fp[k]
    return out


#: family name -> adapter(state, layout) -> migrated state. Families
#: register at import; ``reshard_state`` imports them lazily so the
#: registry is always populated by first use (no import cycles: this
#: module never imports the solvers at top level).
_RESHARD_ADAPTERS: dict = {}


def register_reshard_adapter(family: str, adapter) -> None:
    """Register one durable-state family's migration adapter. The
    adapter takes ``(state, layout)`` — the persisted payload dict and
    the target ``SpecLayout`` — and returns a NEW payload whose
    accumulators are bit-identical and whose mesh manifest names the
    target layout; it raises ``MeshMismatchError`` for payloads it can
    prove torn/partial (those must keep the typed refusal)."""
    _RESHARD_ADAPTERS[family] = adapter


def _infer_reshard_family(state) -> Optional[str]:
    """Which durable-state family a payload dict belongs to, from its
    key shape (each family's snapshot schema is disjoint)."""
    if not isinstance(state, dict):
        return None
    keys = set(state)
    if {"pipeline_digest", "digests", "rows"} <= keys:
        return "profile"
    if {"fingerprint", "gram", "atb"} <= keys:
        if {"x_sum", "y_sum"} <= keys:
            return "online_state"
        if "chunks_done" in keys:
            return "stream_solve"
    if {"fingerprint", "epoch", "W", "R"} <= keys:
        return "bcd_stream" if "block" in keys else "bcd_epoch"
    return None


def reshard_state(state, new_layout: Optional[SpecLayout] = None,
                  family: Optional[str] = None):
    """Migrate one durable-state payload onto ``new_layout``'s mesh
    width — the elastic-mesh recovery every checkpointing family shares.

    The retained f64 accumulators are placement-free by construction
    (gram/AᵀB/col_sums are psum'd sums whose grouping invariance PR 14
    pinned), so migration is a manifest rewrite, not a recompute: the
    per-family adapter re-folds/re-pads anything mesh-shaped (e.g. the
    BCD residual's padded rows), rewrites the fingerprint's mesh keys
    (``MESH_FP_KEYS``) onto the new layout, and returns a NEW payload
    bit-identical in every accumulator byte. A migrated resume therefore
    matches an uninterrupted fresh fit at the target width bit-for-bit.

    ``family`` names the adapter explicitly; None infers it from the
    payload's key shape. Every migration is counted in the "elastic"
    metrics registry family and logged — never silent. Truly
    non-migratable state (unknown family, torn/partial per-shard
    payloads) raises the typed ``MeshMismatchError`` instead.
    """
    import logging

    # Importing the families registers their adapters (see
    # register_reshard_adapter); lazy so there is no import cycle.
    import keystone_tpu.linalg.bcd  # noqa: F401
    import keystone_tpu.linalg.normal_equations  # noqa: F401
    import keystone_tpu.workflow.online  # noqa: F401
    import keystone_tpu.workflow.profile_store  # noqa: F401
    from keystone_tpu.utils.metrics import elastic_counters

    if new_layout is None:
        new_layout = SpecLayout.for_mesh()
    if family is None:
        family = _infer_reshard_family(state)
    adapter = _RESHARD_ADAPTERS.get(family)
    if adapter is None:
        elastic_counters.bump("migrations_refused")
        raise MeshMismatchError(
            f"reshard_state: no migration adapter for this state "
            f"(family={family!r}); it cannot be migrated across mesh "
            "widths — re-run on the recording mesh width"
        )
    migrated = adapter(state, new_layout)
    elastic_counters.bump("states_migrated")
    elastic_counters.bump(f"{family}_migrated")
    logging.getLogger("keystone_tpu").warning(
        "elastic mesh: migrated %s state onto %d-shard mesh "
        "(counted in metrics family 'elastic')",
        family, new_layout.num_shards,
    )
    return migrated


#: Filename of the JSON mesh sidecar every checkpoint writer drops next
#: to its payloads — the static lint's (KG107) no-execution window into
#: what mesh a directory's state was folded under.
MESH_MANIFEST_NAME = "mesh_manifest.json"


def write_mesh_manifest(ckpt_dir: str, fingerprint) -> None:
    """Atomic JSON sidecar naming the mesh a checkpoint directory's state
    was folded under (the fingerprint is JSON-safe scalars by
    construction), so the static lint (KG107) can flag a width drift with
    one dict read — no unpickling, no orbax restore, no execution.
    Best-effort: a read-only store keeps its payloads authoritative."""
    import json
    import os

    path = os.path.join(os.path.abspath(ckpt_dir), MESH_MANIFEST_NAME)
    tmp = path + ".tmp"
    try:
        with open(tmp, "w") as f:
            json.dump(dict(fingerprint), f)
        os.replace(tmp, path)
    except (OSError, TypeError, ValueError):
        try:
            os.unlink(tmp)
        except OSError:
            pass


def read_mesh_manifest(ckpt_dir) -> Optional[dict]:
    """The sidecar's fingerprint dict, or None when absent/unreadable —
    the advisory read; payload fingerprints stay authoritative at
    resume."""
    import json
    import os

    if not ckpt_dir:
        return None
    path = os.path.join(os.path.abspath(str(ckpt_dir)), MESH_MANIFEST_NAME)
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError, UnicodeDecodeError):
        return None
    return doc if isinstance(doc, dict) else None


def reshard_refused(where: str, reason: str) -> MeshMismatchError:
    """The non-migratable refusal adapters raise: counted (never silent)
    and worded like every other mesh refusal, naming the recovery."""
    from keystone_tpu.utils.metrics import elastic_counters

    elastic_counters.bump("migrations_refused")
    return MeshMismatchError(
        f"{where}: state cannot be migrated across mesh widths "
        f"({reason}); reshard_state refuses rather than resume a "
        "corrupted payload — re-run on the recording mesh width or "
        "delete the checkpoint after inspecting it"
    )


def value_data_shards(value) -> Optional[int]:
    """How many data shards a node output spans: the layout's width for
    row-sharded device arrays, 1 for any other placed ``jax.Array``
    (replicated/single-device), None for host values — the profile row's
    mesh-width provenance, a dict read, never a device sync."""
    layout = layout_of_array(value)
    if layout is not None:
        return layout.num_shards
    return 1 if isinstance(value, jax.Array) else None


def batch_layout(x) -> Optional[SpecLayout]:
    """The layout a fused chain should lower with for input ``x``, or None
    for the plain (propagation) path.

    - An already row-sharded device array (the DatasetOperator placement)
      returns its own layout: the chain re-lowers with those explicit
      specs instead of trusting propagation.
    - A host numeric batch at or above ``config.shard_min_rows`` rows
      returns the default layout: the chain call STAGES it onto the mesh
      itself (``put`` for the divisible "shard" class, ``pad_put`` +
      trim for the "pad" class — the old silent single-device cliff) and
      owns the staging copy, which is what makes it donatable into the
      lowered chain (``config.donate_buffers``). Host arrivals are the
      streamed-fit common case: the jittable tail of a mixed chain takes
      its input from the host stage before it.
    - Everything else (sub-minimum batches, non-numeric data, 1-share
      meshes) returns None.
    """
    layout = layout_of_array(x)
    if layout is not None:
        return layout
    if isinstance(x, jax.Array):  # placed already (replicated/one device)
        return None
    if host_batch_shard_class(x) not in ("pad", "shard"):
        # Small / non-numeric batches have nothing to stage.
        return None
    return SpecLayout.for_mesh()
