"""The mesh-sharded SERVING plane: mesh specs, plans, and placements.

``parallel/{mesh,sharded,ring}.py`` give training/offline code the full
scaling-book toolbox. This module is the narrow serving-side facade the
pipeline uses: a ``tensor_filter``'s (or fused region's) ``mesh=`` property
names a mesh spec here, and everything that CONSTRUCTS a sharding on its
behalf — batch shardings for frame I/O, replicated/rule-based weight
placements, reshard moves — lives behind these helpers. Lint rule NNS117
enforces exactly that: ``NamedSharding``/``shard_map``/``pjit`` built
outside ``parallel/`` is a finding, so every sharding decision stays
auditable in one package.

Mesh-spec grammar
-----------------
``<axis><size>`` tokens joined with ``x``; axes are the framework's
canonical mesh axes (``dp``/``tp``/``sp``/``ep``/``pp``, see
``parallel.mesh``); size ``-1`` (or ``*``) means "the rest of the
devices". Examples::

    mesh=dp4        # 4-way batch (data) parallel
    mesh=dp8        # the CI multi-device smoke (8 virtual CPU devices)
    mesh=dp2xtp2    # 2-way batch over a 2x2 mesh, weights replicated
                    # over tp unless the backend supplies param specs
    mesh=dp-1       # batch-shard over every visible device

Serving semantics: the LEADING (batch) dimension of every frame tensor
shards over ``dp``; weights replicate over the whole mesh (one full copy
per chip — which is exactly what the per-shard residency units account).
Axes other than ``dp`` exist so GSPMD programs with real param specs
(``parallel.sharded``) can ride the same mesh.

Matched-sharding contract
-------------------------
Two sharded regions hand DeviceBuffers to each other through
device-passthrough elements (queues). The hand-off moves ZERO bytes iff
the producer's out-sharding equals the consumer's in-sharding —
``pipeline/fuse.py`` verifies that at PLAN time (a mismatch is a hard
:class:`MeshShardingError` before any frame flows, per SNIPPETS [1]'s
pjit-to-pjit matched-sharding rule). Any RUNTIME placement that does move
device bytes between shardings goes through :func:`place_batch`, which
counts them in ``nns_reshard_bytes_total`` — the counter that must stay 0
across matched boundaries.

Kill switch: ``NNSTPU_MESH=0`` (or no ``mesh=`` property anywhere) keeps
:func:`mesh_enabled` False; every caller then behaves byte-identically to
the single-device path — the ``NNSTPU_FAULTS``/``NNSTPU_TRACE``/
``NNSTPU_HBM_BUDGET`` kill-switch discipline.
"""

from __future__ import annotations

import itertools
import os
import threading
import weakref
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from nnstreamer_tpu.log import get_logger
from nnstreamer_tpu.parallel.mesh import make_mesh
from nnstreamer_tpu.tensors import memory as _memory

log = get_logger("mesh-serve")

_ENV = "NNSTPU_MESH"

#: canonical mesh axis names, in the order parallel/mesh.py documents them
MESH_AXES = ("dp", "tp", "sp", "ep", "pp")

#: buffer meta key: the canonical mesh spec whose plan produced the
#: buffer's (sharded) device tensors — stamped by sharded fused regions
MESH_SPEC_META = "mesh-spec"


class MeshShardingError(RuntimeError):
    """A sharding contract violation caught at PLAN time: mismatched
    in/out shardings across a device-passthrough boundary, mixed mesh
    specs inside one fused region, or an unparseable spec. Deliberately
    NOT a FlowError — fusion fallback must not swallow it."""


def mesh_enabled() -> bool:
    """The ``NNSTPU_MESH`` kill switch (default ON — the mesh only
    engages where a ``mesh=`` property asks for it anyway)."""
    return os.environ.get(_ENV, "1").strip().lower() not in (
        "0", "false", "no", "off"
    )


def parse_mesh_spec(spec: str) -> List[Tuple[str, int]]:
    """``"dp2xtp2"`` → ``[("dp", 2), ("tp", 2)]`` (see module docstring
    for the grammar). Raises :class:`MeshShardingError` on malformed
    specs so a typo is a plan-time error, not a silent single-device
    fallback."""
    text = str(spec or "").strip().lower()
    if not text:
        raise MeshShardingError("empty mesh spec")
    axes: List[Tuple[str, int]] = []
    seen = set()
    for token in text.split("x"):
        token = token.strip()
        name = None
        for cand in MESH_AXES:
            if token.startswith(cand):
                name = cand
                break
        if name is None:
            raise MeshShardingError(
                f"mesh spec {spec!r}: token {token!r} does not start with "
                f"one of the mesh axes {'/'.join(MESH_AXES)}")
        if name in seen:
            raise MeshShardingError(
                f"mesh spec {spec!r}: duplicate axis {name!r}")
        seen.add(name)
        size_text = token[len(name):]
        if size_text in ("*", ""):
            size = -1
        else:
            try:
                size = int(size_text)
            except ValueError:
                raise MeshShardingError(
                    f"mesh spec {spec!r}: bad size {size_text!r} for axis "
                    f"{name!r}") from None
        if size == 0 or size < -1:
            raise MeshShardingError(
                f"mesh spec {spec!r}: axis {name!r} size must be positive "
                f"or -1, got {size}")
        axes.append((name, size))
    return axes


class MeshPlan:
    """One parsed-and-built mesh spec: the Mesh plus the (cached)
    NamedShardings serving needs. Implements the same ``batched()`` /
    ``replicated()`` / ``num_devices`` surface as
    ``parallel.mesh.BatchSharding`` so filter backends treat either as
    "the sharding"."""

    def __init__(self, spec: str):
        self.spec = canonical_spec(spec)
        self.axes = parse_mesh_spec(spec)
        self.mesh = make_mesh(self.axes)
        self._batched = None
        self._replicated = None

    @property
    def shard_count(self) -> int:
        """Total devices in the mesh (= the dp fan-out times any inner
        axes; what ``nns_shard_count`` reports)."""
        return int(self.mesh.size)

    @property
    def num_devices(self) -> int:  # BatchSharding-compatible alias
        return self.shard_count

    @property
    def batch_axis(self) -> Optional[str]:
        return "dp" if any(n == "dp" for n, _ in self.axes) else None

    @property
    def dp_size(self) -> int:
        return int(self.mesh.shape["dp"]) if self.batch_axis else 1

    def sharding_for(self, x):
        """The placement for one frame tensor: :meth:`batched` when its
        leading dim splits evenly over ``dp``, else :meth:`replicated`
        — a ragged or sub-mesh batch (e.g. a flush tail, or a
        single-frame pipeline someone slapped ``mesh=dp8`` on) runs
        replicated instead of erroring. The mesh must never make a
        legal single-device pipeline illegal; it only speeds up the
        batches that actually split."""
        shape = getattr(x, "shape", None)
        if self.batch_axis and shape and len(shape) >= 1 \
                and shape[0] % self.dp_size == 0:
            return self.batched()
        return self.replicated()

    def batched(self):
        """Leading-dim (batch) sharding over ``dp``; replicated when the
        mesh has no dp axis (still a valid — if pointless — plan)."""
        if self._batched is None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            self._batched = NamedSharding(
                self.mesh, P(self.batch_axis) if self.batch_axis else P())
        return self._batched

    def replicated(self):
        if self._replicated is None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            self._replicated = NamedSharding(self.mesh, P())
        return self._replicated

    def __repr__(self):
        return f"<MeshPlan {self.spec} {dict(self.mesh.shape)}>"


def canonical_spec(spec: str) -> str:
    """Normalized spec text (lowercased, stripped) — the comparison key
    for the matched-sharding contract and the plan cache."""
    return str(spec or "").strip().lower()


#: plan cache: building a Mesh enumerates devices; one plan per spec per
#: process (jax's device set is process-global, so this never goes stale)
_plans: Dict[str, MeshPlan] = {}
_plans_lock = threading.Lock()


def get_mesh_plan(spec: str) -> MeshPlan:
    key = canonical_spec(spec)
    with _plans_lock:
        plan = _plans.get(key)
    if plan is not None:
        return plan
    # build OUTSIDE the lock (mesh construction enumerates devices);
    # a racing builder loses to setdefault and its plan is dropped —
    # plans for one spec are interchangeable, so that is harmless
    built = MeshPlan(key)
    with _plans_lock:
        plan = _plans.setdefault(key, built)
    if plan is built:
        # the reshard counter exports (at 0) as soon as any mesh plan
        # exists: the matched-boundary CI gate asserts on it
        _reshard_counter()
        log.info("mesh plan %s: %d devices %s", key, plan.shard_count,
                 dict(plan.mesh.shape))
    return plan


# --------------------------------------------------------------------------
# reshard accounting — nns_reshard_bytes_total
# --------------------------------------------------------------------------
_m_reshard = None


def _reshard_counter():
    global _m_reshard
    if _m_reshard is None:
        from nnstreamer_tpu.obs import get_registry

        _m_reshard = get_registry().counter(
            "nns_reshard_bytes_total",
            "Device bytes moved to FIX a sharding mismatch at runtime "
            "(device array re-placed onto a different sharding). Stays 0 "
            "across matched fused-region boundaries — the zero-copy "
            "hand-off contract.")
    return _m_reshard


def reshard_bytes_total() -> int:
    """Current counter value (0 when no mesh plan ever resharded)."""
    return int(_m_reshard.value) if _m_reshard is not None else 0


def shardings_match(a, b) -> bool:
    """Whether two shardings place data identically (the zero-copy
    hand-off test). None compares unequal to everything."""
    if a is None or b is None:
        return False
    try:
        return bool(a == b)
    except Exception:  # noqa: BLE001 — foreign sharding types: not equal
        return False


def place_batch(x, plan: MeshPlan, shard_span: Optional[list] = None):
    """Place one frame tensor for a sharded invoke.

    - already a device array with the plan's batch sharding → returned
      as-is, ZERO bytes moved (the matched hand-off fast path);
    - a device array with any OTHER sharding → re-placed, and the moved
      bytes count into ``nns_reshard_bytes_total``;
    - a host array → plain H2D upload (counted upstream at
      to_device/upload_many like every other ingest transfer, NOT a
      reshard).

    ``shard_span``, when given, collects ``(kind, nbytes)`` tuples so the
    caller can emit one flight-recorder ``shard`` span per invoke."""
    import jax

    tgt = plan.sharding_for(x)
    if isinstance(x, jax.Array):
        if shardings_match(getattr(x, "sharding", None), tgt):
            return x
        moved = int(getattr(x, "nbytes", 0))
        _reshard_counter().inc(moved)
        if shard_span is not None:
            shard_span.append(("reshard", moved))
        return jax.device_put(x, tgt)  # nns-lint: disable=NNS113 -- counted above in nns_reshard_bytes_total; the frame's H2D bytes were tracked at its original upload
    if shard_span is not None:
        shard_span.append(("scatter", int(getattr(x, "nbytes", 0))))
    return jax.device_put(x, tgt)  # nns-lint: disable=NNS113 -- transient invoke input scatter; the frame's bytes are tracked upstream at to_device/upload_many


# --------------------------------------------------------------------------
# weight placement + per-shard accounting
# --------------------------------------------------------------------------
_place_ids = itertools.count()


def _per_device_nbytes(leaves) -> Dict[Any, int]:
    """Actual bytes each mesh device holds for ``leaves`` (from the
    arrays' addressable shards — exact for replicated AND rule-sharded
    placements)."""
    per: Dict[Any, int] = {}
    for leaf in leaves:
        shards = getattr(leaf, "addressable_shards", None)
        if not shards:
            continue
        for sh in shards:
            per[sh.device] = per.get(sh.device, 0) + int(sh.data.nbytes)
    return per


def account_placement(placed: Any, label: str, owner: Any = None) -> None:
    """Register an externally-held sharded placement's per-device bytes
    with the active HBM accountant as PINNED per-shard residency units
    (satellite of NNS113: the bytes show in ``nns_mem_used_bytes``
    instead of hiding behind a pragma). The units un-register when the
    placed pytree dies — they are accounting, not an eviction target,
    because the caller (a train step, the serving engine) holds the
    arrays and an eviction here could not actually free them. ``owner``
    names whose death retires them instead, where the tree may share
    arrays with someone who outlives it (the serving engine passes
    through what it is given in the dtype it reads)."""
    acct = _memory.ACTIVE
    if acct is None:
        return
    import jax

    leaves = [x for x in jax.tree.leaves(placed)
              if hasattr(x, "addressable_shards")]
    if not leaves:
        return
    per = _per_device_nbytes(leaves)
    if not per:
        return
    base = f"place:{next(_place_ids)}:{label}"
    keys = []
    for k, (_dev, nbytes) in enumerate(sorted(
            per.items(), key=lambda kv: str(kv[0]))):
        key = f"{base}:shard{k}"
        acct.residency.adopt(key, nbytes, label=f"{label}#shard{k}")
        keys.append(key)
    try:
        weakref.finalize(leaves[0] if owner is None else owner,
                         _release_placement,
                         weakref.ref(acct), tuple(keys))
    except TypeError:
        # not weakref-able (unexpected for jax arrays): count the
        # placement but release immediately rather than leak forever
        _release_placement(weakref.ref(acct), tuple(keys))


def _release_placement(acct_ref, keys: Tuple[str, ...]) -> None:
    """Module-level finalizer target: retire a dead placement's pinned
    units against the SAME accountant that adopted them."""
    acct = acct_ref()
    if acct is None:
        return
    for key in keys:
        acct.residency.unregister(key)


def place_params(params: Dict[str, Any], mesh, specs: Dict[str, Any],
                 label: str = "params") -> Dict[str, Any]:
    """Rule-sharded param placement WITH accounting: device_put each
    entry per its PartitionSpec and register the per-shard HBM with the
    budget accountant (when active). This is the sanctioned home for
    what used to be raw ``jax.device_put(v, NamedSharding(...))`` sites
    in ``parallel/sharded.py`` and ``serving/engine.py``."""
    import jax
    from jax.sharding import NamedSharding

    placed = {
        k: jax.device_put(v, NamedSharding(mesh, specs[k]))  # nns-lint: disable=NNS113 -- the per-shard bytes register with the accountant two lines down (account_placement)
        for k, v in params.items()
    }
    account_placement(placed, label)
    return placed


def place_tree(tree: Any, mesh, spec_of: Callable[[Any], Any],
               label: str = "tree", register: bool = False) -> Any:
    """Mesh placement for an arbitrary pytree: ``spec_of(leaf)`` names
    each leaf's PartitionSpec. ``register=True`` additionally accounts
    the per-shard bytes (off by default — e.g. a KV cache is working
    state the engine resizes on its own schedule)."""
    import jax
    from jax.sharding import NamedSharding

    placed = jax.tree.map(
        lambda a: jax.device_put(a, NamedSharding(mesh, spec_of(a))),  # nns-lint: disable=NNS113 -- sharded placement helper; callers opt into accounting via register=True
        tree)
    if register:
        account_placement(placed, label)
    return placed
