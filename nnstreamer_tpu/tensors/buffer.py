"""TensorBuffer — one stream frame: N tensors + timing metadata.

The reference flows ``GstBuffer``s holding up to 16 ``GstMemory`` chunks
(one per tensor) with pts/dts/duration and attachable metas
(``gst/nnstreamer/tensor_meta.c``). Here a frame is a list of *arrays* —
host ``numpy.ndarray`` or device-resident ``jax.Array`` — so tensors can stay
in TPU HBM as they flow between elements (the reference's zero-copy
``GstMemory`` mapping, ``tensor_filter.c:585-604``, maps to "never leave the
device"). Host/device placement is explicit via :meth:`to_device` /
:meth:`to_host`; elements that only reorder/route tensors never touch bytes.

``meta`` carries attachable per-buffer metadata the way GstMeta does — e.g.
the query client id used by the distributed serversink to route results
(reference ``GstMetaQuery``, tensor_meta.c), or crop regions.
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
import weakref
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from nnstreamer_tpu.obs import timeline as _timeline
from nnstreamer_tpu.tensors.types import (
    NNS_TENSOR_SIZE_LIMIT,
    TensorsInfo,
)

#: Sentinel for "no timestamp" (reference GST_CLOCK_TIME_NONE).
CLOCK_NONE: Optional[int] = None


def residency_enabled() -> bool:
    """Global off-switch for the device-residency layer. With
    ``NNSTPU_RESIDENT=0`` no :class:`DeviceBuffer` is ever created and
    every element sees plain host-materialized buffers, which is the
    byte-equality reference the residency tests compare against."""
    return os.environ.get("NNSTPU_RESIDENT", "1").strip().lower() not in (
        "0", "false", "no", "off"
    )


# -- transfer accounting ------------------------------------------------------
# Process-wide tallies of explicit host<->device copies plus the pad-entry
# residency split, mirrored into obs/ as nns_transfer_h2d_bytes_total /
# nns_transfer_d2h_bytes_total counters and the nns_buffer_resident_ratio
# gauge. bench.py reads transfer_snapshot() deltas per run (d2h_per_frame).
_xfer_lock = threading.Lock()
_xfer: Dict[str, float] = {
    "h2d_bytes": 0.0, "h2d_events": 0.0,
    "d2h_bytes": 0.0, "d2h_events": 0.0,
    # staged multi-frame window transfers (one device_put / device_get
    # covering a whole dispatch window): *_events counts uploads/fetches,
    # *_frames the frames they carried. Per-frame h2d_events/d2h_events
    # deliberately do NOT move for these — d2h_per_frame / h2d_per_frame
    # measure per-frame round trips, which window batching exists to
    # drive to zero (the bytes still land in h2d_bytes/d2h_bytes).
    "h2d_batched_events": 0.0, "h2d_batched_frames": 0.0,
    "d2h_batched_events": 0.0, "d2h_batched_frames": 0.0,
    "resident_entries": 0.0, "materialized_entries": 0.0,
}
_xfer_metrics: Optional[Dict[str, Any]] = None


def _xfer_obs() -> Dict[str, Any]:
    global _xfer_metrics
    if _xfer_metrics is None:
        from nnstreamer_tpu.obs import get_registry

        reg = get_registry()
        _xfer_metrics = {
            "h2d": reg.counter(
                "nns_transfer_h2d_bytes_total",
                "Bytes explicitly uploaded host->device "
                "(TensorBuffer.to_device)"),
            "d2h": reg.counter(
                "nns_transfer_d2h_bytes_total",
                "Bytes explicitly materialized device->host (to_host)"),
            "h2d_batched": reg.counter(
                "nns_transfer_batched_h2d_total",
                "Staged multi-frame slab uploads: one device_put "
                "carrying a whole dispatch window (upload_many)"),
            "d2h_batched": reg.counter(
                "nns_transfer_batched_d2h_total",
                "Grouped drain-side fetches: one device_get carrying a "
                "whole materialization run (materialize_many)"),
        }
        reg.gauge(
            "nns_buffer_resident_ratio",
            "Fraction of DeviceBuffer pad entries forwarded without host "
            "materialization",
            fn=lambda: resident_ratio() or 0.0)
    return _xfer_metrics


def _record_h2d(nbytes: int) -> None:
    if nbytes <= 0:
        return
    _xfer_obs()["h2d"].inc(nbytes)
    with _xfer_lock:
        _xfer["h2d_bytes"] += nbytes
        _xfer["h2d_events"] += 1


def _record_d2h(nbytes: int) -> None:
    if nbytes <= 0:
        return
    _xfer_obs()["d2h"].inc(nbytes)
    with _xfer_lock:
        _xfer["d2h_bytes"] += nbytes
        _xfer["d2h_events"] += 1


def _record_h2d_batched(frames: int, nbytes: int) -> None:
    """One staged multi-frame slab upload: bytes land in the cumulative
    h2d byte tally, but the per-frame event counter does not move — the
    whole point of the window slab is that these frames paid no
    per-frame round trip."""
    if nbytes <= 0:
        return
    obs = _xfer_obs()
    obs["h2d"].inc(nbytes)
    obs["h2d_batched"].inc()
    with _xfer_lock:
        _xfer["h2d_bytes"] += nbytes
        _xfer["h2d_batched_events"] += 1
        _xfer["h2d_batched_frames"] += frames


def _record_d2h_batched(frames: int, nbytes: int) -> None:
    if nbytes <= 0:
        return
    obs = _xfer_obs()
    obs["d2h"].inc(nbytes)
    obs["d2h_batched"].inc()
    with _xfer_lock:
        _xfer["d2h_bytes"] += nbytes
        _xfer["d2h_batched_events"] += 1
        _xfer["d2h_batched_frames"] += frames


def _tl_xfer_span(kind: str, meta: Dict[str, Any], t0: float,
                  nbytes: int = 0) -> None:
    """Record a transfer span (``h2d``/``d2h``) on the active timeline
    for the frame carried in ``meta`` — free single-test no-op when
    tracing is off or the buffer predates the source's seq stamp."""
    tl = _timeline.ACTIVE
    if tl is None:
        return
    seq = meta.get(_timeline.TRACE_SEQ_META)
    if seq is None:
        return
    tl.span(kind, seq, t0, time.monotonic(), track="transfer",
            nbytes=nbytes)


def _fault_check(site: str, meta: Dict[str, Any]) -> None:
    """Transfer-site chaos hook (pipeline/faults.py), resolved through
    ``sys.modules`` so the tensors layer never imports the pipeline
    package (element.py imports this module — a top-level import back
    would cycle). With injection off this is one dict lookup; an
    injector can only exist once its module is imported, so the lazy
    resolution can never miss an active one."""
    import sys

    faults = sys.modules.get("nnstreamer_tpu.pipeline.faults")
    if faults is None or faults.ACTIVE is None:
        return
    faults.ACTIVE.check(site, seq=meta.get(_timeline.TRACE_SEQ_META))


def _mem_note_h2d(nbytes: int, owner) -> None:
    """Register an H2D transfer's bytes with the HBM budget accountant
    (``tensors/memory.py``); ``owner`` is the Python buffer wrapper whose
    death releases the device payload, so the accountant's ``frames``
    category tracks the live device working set. Same ``sys.modules``
    kill-switch shape as :func:`_fault_check`: no accountant, one dict
    lookup, out."""
    import sys

    mem = sys.modules.get("nnstreamer_tpu.tensors.memory")
    if mem is None or mem.ACTIVE is None:
        return
    mem.ACTIVE.note_h2d(nbytes, owner)


def record_residency_entry(resident: bool) -> None:
    """Tally one DeviceBuffer pad entry: ``resident`` means the element
    declared DEVICE_PASSTHROUGH and the buffer crossed the pad without a
    host copy (the numerator of ``nns_buffer_resident_ratio``)."""
    _xfer_obs()  # the gauge is registered with the counters
    with _xfer_lock:
        key = "resident_entries" if resident else "materialized_entries"
        _xfer[key] += 1


def resident_ratio() -> Optional[float]:
    with _xfer_lock:
        r = _xfer["resident_entries"]
        m = _xfer["materialized_entries"]
    total = r + m
    return (r / total) if total else None


def transfer_snapshot() -> Dict[str, float]:
    """Copy of the cumulative transfer tallies (bytes + event counts +
    entry split); callers diff two snapshots for per-run numbers."""
    with _xfer_lock:
        return dict(_xfer)


def _device_nbytes(t) -> int:
    return int(np.prod(t.shape, dtype=np.int64)) * np.dtype(t.dtype).itemsize


import functools


@functools.lru_cache(maxsize=256)
def _pad_rows_fn(r: int, shape: tuple, dtype: str):
    """Jitted axis-0 zero-pad, cached per (pad, shape, dtype) so each
    partial-window size costs one small compile, then one fused device
    dispatch per tensor (see TensorBuffer.pad_rows_device)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        return jnp.concatenate(
            [x, jnp.zeros((r,) + tuple(shape[1:]), x.dtype)], axis=0)

    return f


def is_device_array(x) -> bool:
    """True if ``x`` is a jax.Array (device-resident)."""
    import jax

    return isinstance(x, jax.Array)


def _host_owned(t) -> np.ndarray:
    """D2H that OWNS its bytes. ``np.asarray`` on a CPU jax array can be
    a zero-copy view into the XLA buffer; once that buffer is released
    (dispatch-window fence) and its memory reused, the view silently
    reads the NEXT tenant's bytes. Donating fused programs make this
    real: a persistent-cache-deserialized executable keeps its
    input-output aliasing (the in-process compile drops it for host
    inputs), so warm-boot outputs live in donated slabs with exactly
    that lifetime. Real accelerators already return owning arrays here,
    so the copy triggers only where the aliasing hazard exists."""
    v = np.asarray(t)
    if v.base is not None or not v.flags.owndata:
        v = np.array(v)  # defensive copy: detach from the XLA buffer
    return v


@dataclasses.dataclass
class TensorBuffer:
    """One frame of a tensor stream.

    Attributes
    ----------
    tensors : list of numpy.ndarray or jax.Array
    pts, dts, duration : int nanoseconds, or None (unset)
    meta : free-form attachable metadata (GstMeta equivalent)
    """

    tensors: List[Any] = dataclasses.field(default_factory=list)
    pts: Optional[int] = None
    dts: Optional[int] = None
    duration: Optional[int] = None
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)
    #: deferred host-side completion: ``fn(host_buf) -> TensorBuffer``,
    #: applied by :meth:`to_host` after tensors materialize. Lets a fused
    #: region keep a decoder's math on device (argmax, box select) and
    #: delay its host-only part (label strings, overlay compose) to the
    #: sink's fetch point — so no element forces a blocking D2H mid-stream.
    finalize: Optional[Any] = None

    def __post_init__(self):
        if len(self.tensors) > NNS_TENSOR_SIZE_LIMIT:
            raise ValueError(
                f"{len(self.tensors)} tensors exceeds {NNS_TENSOR_SIZE_LIMIT}"
            )

    # -- constructors -------------------------------------------------------
    @classmethod
    def from_arrays(cls, arrays: Sequence, pts: Optional[int] = None, **kw):
        return cls(tensors=list(arrays), pts=pts, **kw)

    @classmethod
    def wall_clock_pts(cls) -> int:
        return time.monotonic_ns()

    # -- container protocol --------------------------------------------------
    def __len__(self):
        return len(self.tensors)

    def __getitem__(self, i):
        return self.tensors[i]

    def __iter__(self):
        return iter(self.tensors)

    # -- derived -------------------------------------------------------------
    @property
    def num_tensors(self) -> int:
        return len(self.tensors)

    def tensors_info(self) -> TensorsInfo:
        return TensorsInfo.from_arrays(self.tensors)

    def nbytes(self) -> int:
        return sum(int(np.prod(t.shape)) * t.dtype.itemsize for t in self.tensors)

    def create_stamps(self):
        """Capture timestamps carried in meta for end-to-end latency:
        the plural ``create_ts`` (aggregated/muxed frames, one stamp per
        constituent frame) or the singular ``create_t`` a source
        stamped. Returns a (possibly empty) list."""
        stamps = self.meta.get("create_ts")
        if stamps:
            return list(stamps)
        if "create_t" in self.meta:
            return [self.meta["create_t"]]
        return []

    def on_device(self) -> bool:
        return bool(self.tensors) and all(is_device_array(t) for t in self.tensors)

    # -- placement -----------------------------------------------------------
    def to_host(self) -> "TensorBuffer":
        """Materialize all tensors as numpy arrays (blocking D2H if needed),
        then apply the deferred ``finalize`` hook if one is attached."""
        t0 = time.monotonic()
        out, moved = [], 0
        for t in self.tensors:
            if isinstance(t, np.ndarray):
                out.append(t)
            else:
                out.append(_host_owned(t))
                moved += _device_nbytes(t)
        if moved:
            _fault_check("transfer.d2h", self.meta)
            _record_d2h(moved)
            _tl_xfer_span("d2h", self.meta, t0, nbytes=moved)
        buf = self.replace(tensors=out, finalize=None)
        if self.finalize is not None:
            buf = self.finalize(buf)
        return buf

    def to_device(self, device=None, sharding=None) -> "TensorBuffer":
        """Move all tensors onto a JAX device (or sharding). ``sharding``
        may be a callable ``tensor -> sharding`` for placements that
        depend on each tensor's shape (a serving MeshPlan's
        ``sharding_for``)."""
        import jax

        t0 = time.monotonic()
        moved = sum(_device_nbytes(t) for t in self.tensors
                    if not is_device_array(t))
        if callable(sharding):
            out = [jax.device_put(t, sharding(t)) for t in self.tensors]
        else:
            tgt = sharding if sharding is not None else device
            out = [jax.device_put(t, tgt) for t in self.tensors]
        buf = self.replace(tensors=out)
        if moved:
            _fault_check("transfer.h2d", self.meta)
            _record_h2d(moved)
            _tl_xfer_span("h2d", self.meta, t0, nbytes=moved)
            _mem_note_h2d(moved, buf)
        return buf

    def pad_rows_device(self) -> "TensorBuffer":
        """Apply a deferred partial-window pad (aggregator
        ``pad-device``): zero-pad ``meta["pad_rows"]`` leading-axis rows
        onto each (device-resident) tensor with one tiny jitted program
        per (shape, pad) — the pad rows never cross the H2D link, and
        the downstream jitted consumer keeps its single full-window
        compiled shape. No-op without the meta key."""
        r = self.meta.get("pad_rows")
        if not r:
            return self
        out = [_pad_rows_fn(int(r), t.shape, str(t.dtype))(t)
               for t in self.tensors]
        meta = dict(self.meta)
        del meta["pad_rows"]
        return self.replace(tensors=out, meta=meta)

    def block_until_ready(self) -> "TensorBuffer":
        for t in self.tensors:
            if is_device_array(t):
                t.block_until_ready()
        return self

    # -- functional update ----------------------------------------------------
    def replace(self, **kw) -> "TensorBuffer":
        """Copy with replaced fields; tensors list is shallow-copied, meta is
        copied (buffers are treated as immutable once pushed)."""
        fields = dict(
            tensors=list(self.tensors),
            pts=self.pts,
            dts=self.dts,
            duration=self.duration,
            meta=dict(self.meta),
            finalize=self.finalize,
        )
        fields.update(kw)
        return TensorBuffer(**fields)

    def with_tensors(self, tensors: Sequence) -> "TensorBuffer":
        """New buffer with the same timing/meta but different payload."""
        return self.replace(tensors=list(tensors))

    def __repr__(self):
        shapes = ",".join(
            f"{tuple(t.shape)}:{np.dtype(t.dtype).name}" for t in self.tensors
        )
        dev = "dev" if self.on_device() else "host"
        return f"TensorBuffer([{shapes}] {dev} pts={self.pts})"


# -- device residency ---------------------------------------------------------
def _unpin_tokens(tokens) -> None:
    """weakref.finalize target for a dead DeviceBuffer's pinned host-view
    slabs (module-level so the finalizer holds no reference to the buffer)."""
    from nnstreamer_tpu.tensors.pool import get_pool

    pool = get_pool()
    for t in tokens:
        pool.unpin(t)


class DeviceBuffer(TensorBuffer):
    """A device-resident frame: live ``jax.Array`` payloads that cross pad
    boundaries without touching the host.

    Elements that declare ``DEVICE_PASSTHROUGH`` forward these untouched;
    everything else gets a host-materialized copy at pad entry (see
    ``Element._chain_entry``). The host side is *lazy and cached*:

    - the first :meth:`to_host` call is the one sanctioned D2H site (lint
      NNS108) — it materializes once, applies ``finalize``, and caches;
      every later call returns the SAME host buffer object;
    - a ``host_view`` — the pre-upload host arrays a prefetching queue
      already holds — makes that first call a zero-copy re-wrap. Pool-owned
      host-view arrays are *pinned* so an explicit ``BufferPool.release``
      (sink/dispatch fence) can never recycle a slab this cache still
      reads; the pin lifts when the wrapper itself dies.
    """

    def __init__(self, tensors=None, pts=None, dts=None, duration=None,
                 meta=None, finalize=None, host_view=None):
        super().__init__(tensors=list(tensors or []), pts=pts, dts=dts,
                         duration=duration, meta=dict(meta or {}),
                         finalize=finalize)
        self._host_cache: Optional[TensorBuffer] = None
        self._host_src: Optional[List[Any]] = None
        if host_view is not None and len(host_view) == len(self.tensors):
            self._adopt_host_view(list(host_view))

    def _adopt_host_view(self, host: List[Any]) -> None:
        from nnstreamer_tpu.tensors.pool import get_pool

        self._host_src = host
        pool = get_pool()
        tokens = tuple(id(a) for a in host if pool.pin(a))
        if tokens:
            weakref.finalize(self, _unpin_tokens, tokens)

    def to_host(self) -> TensorBuffer:
        """The sanctioned materialization point: one D2H (or zero, when a
        pre-upload host view was adopted), finalize applied once, result
        cached and shared by every later caller."""
        cached = self._host_cache
        if cached is not None:
            return cached
        if self._host_src is not None:
            host = list(self._host_src)  # zero-copy: pre-upload bytes
        else:
            t0 = time.monotonic()
            host, moved = [], 0
            for t in self.tensors:
                if isinstance(t, np.ndarray):
                    host.append(t)
                else:
                    host.append(_host_owned(t))
                    moved += _device_nbytes(t)
            if moved:
                _fault_check("transfer.d2h", self.meta)
                _record_d2h(moved)
                _tl_xfer_span("d2h", self.meta, t0, nbytes=moved)
        buf = TensorBuffer(tensors=host, pts=self.pts, dts=self.dts,
                           duration=self.duration, meta=dict(self.meta),
                           finalize=None)
        if self.finalize is not None:
            buf = self.finalize(buf)
        self._host_cache = buf
        return buf

    def replace(self, **kw) -> TensorBuffer:
        """Stays a :class:`DeviceBuffer` while the payload stays on device
        (so routing elements' ``replace()``/``with_tensors()`` don't
        silently demote residency); an unchanged payload keeps the adopted
        host view. The materialized-host cache is never carried over —
        meta/finalize edits would make it stale."""
        fields = dict(
            tensors=list(self.tensors),
            pts=self.pts,
            dts=self.dts,
            duration=self.duration,
            meta=dict(self.meta),
            finalize=self.finalize,
        )
        fields.update(kw)
        tensors = fields["tensors"]
        if tensors and all(is_device_array(t) for t in tensors):
            host_view = self._host_src if "tensors" not in kw else None
            return DeviceBuffer(host_view=host_view, **fields)
        return TensorBuffer(**fields)

    def __repr__(self):
        base = super().__repr__()
        state = ("view" if self._host_src is not None else
                 "cached" if self._host_cache is not None else "lazy")
        return base.replace("TensorBuffer(", f"DeviceBuffer(host={state} ", 1)


def as_device_buffer(buf: TensorBuffer, host_view=None) -> TensorBuffer:
    """Wrap an all-device buffer as a :class:`DeviceBuffer`; returns the
    input unchanged when residency is disabled, the payload is not fully
    on device, or it is already wrapped."""
    if isinstance(buf, DeviceBuffer) or not residency_enabled():
        return buf
    if not buf.on_device():
        return buf
    return DeviceBuffer(tensors=buf.tensors, pts=buf.pts, dts=buf.dts,
                        duration=buf.duration, meta=buf.meta,
                        finalize=buf.finalize, host_view=host_view)


# -- staged multi-frame window transfers --------------------------------------
#: meta key marking a buffer whose device payload was freshly created by
#: an upload point for exactly one downstream consumer — the whole-graph
#: fused region may DONATE such tensors to XLA (pipeline/fuse.py); shared
#: or source-owned payloads never carry it
H2D_EXCLUSIVE_META = "h2d_exclusive"


def upload_many(bufs: List[TensorBuffer]) -> (
        "tuple[List[TensorBuffer], List[np.ndarray]]"):
    """Coalesce one dispatch window's H2D copies into a single staged
    multi-frame slab upload (FaaSTube-style transfer batching).

    For each tensor index the window's frames are assembled into ONE
    contiguous ``(k,) + shape`` host view — zero-copy when the frames are
    already consecutive window-slab slots (``pool.contiguous_window_view``,
    the ingest-lane staging layout), else copied into a fresh pool window
    slab — and cross the link as ONE ``jax.device_put``. Per-frame device
    views are carved device-side (a lazy slice per slot, no extra
    transfers). Returns ``(device_buffers, window_slabs)``: the caller
    stamps the slabs into the LAST buffer's pool stash so the dispatch
    window's fence (``pipeline/dispatch.py``) recycles them only after
    every dispatch that read the upload has completed.

    Callers must pass ≥1 host-resident buffers with identical tensor
    signatures; ordering and per-buffer meta/finalize are preserved, so
    results are byte-identical to per-buffer ``to_device()``.
    """
    import jax

    from nnstreamer_tpu.tensors.pool import (
        contiguous_window_view,
        get_pool,
    )

    k = len(bufs)
    n_t = len(bufs[0].tensors)
    pool = get_pool()
    t0 = time.monotonic()
    _fault_check("transfer.h2d", bufs[0].meta)
    slabs: List[np.ndarray] = []
    stacked_per_tensor: List[np.ndarray] = []
    moved = 0
    for j in range(n_t):
        frames = [b.tensors[j] for b in bufs]
        stacked = contiguous_window_view(frames) if k > 1 else None
        if stacked is None:
            stacked = pool.acquire_window(k, frames[0].shape,
                                          frames[0].dtype)
            for i, f in enumerate(frames):
                np.copyto(stacked[i], f)
            slabs.append(stacked)
        moved += stacked.nbytes
        stacked_per_tensor.append(stacked)
    devs = [jax.device_put(s) for s in stacked_per_tensor]
    _record_h2d_batched(k, moved)
    _tl_xfer_span("h2d_batched", bufs[0].meta, t0, nbytes=moved)
    out: List[TensorBuffer] = []
    for i, b in enumerate(bufs):
        dev_tensors = [devs[j][i] for j in range(n_t)]
        nb = b.with_tensors(dev_tensors)
        nb.meta[H2D_EXCLUSIVE_META] = True
        # the pre-upload host arrays become the wrapper's zero-copy host
        # view, exactly like the per-buffer prefetch path
        wrapped = as_device_buffer(nb, host_view=list(b.tensors))
        # each frame view shares the window's device slabs; the budget
        # accountant sees a per-frame share so the frames category tracks
        # the live working set as views die
        _mem_note_h2d(moved // k, wrapped)
        out.append(wrapped)
    return out, slabs


def materialize_many(bufs: List[TensorBuffer]) -> List[TensorBuffer]:
    """Drain-side grouped materialization: every device tensor across the
    run crosses D2H in ONE ``jax.device_get`` instead of one blocking
    fetch per frame. Results are byte-identical to calling ``to_host()``
    per buffer — per-buffer ``finalize`` hooks run in order on the host
    payloads, DeviceBuffer host caches are honored and filled — but the
    transfer tally records one *batched* fetch (``d2h_batched_events``)
    and zero per-frame round trips, which is what ``d2h_per_frame = 0``
    on a device-decodable pipeline means."""
    import jax

    fetch: List[Any] = []
    where: Dict[Any, int] = {}
    direct: List[bool] = []
    for i, b in enumerate(bufs):
        if isinstance(b, DeviceBuffer) and (
                b._host_cache is not None or b._host_src is not None):
            direct.append(True)  # zero-copy/cached: to_host() is free
            continue
        direct.append(False)
        for j, t in enumerate(b.tensors):
            if not isinstance(t, np.ndarray):
                where[(i, j)] = len(fetch)
                fetch.append(t)
    if fetch:
        t0 = time.monotonic()
        moved = sum(_device_nbytes(t) for t in fetch)
        _fault_check("transfer.d2h", bufs[0].meta)
        # the one sanctioned *batched* D2H: a single grouped fetch for
        # the whole run  # nns-lint: disable=NNS108 -- batched twin of to_host
        fetched = jax.device_get(fetch)
        _record_d2h_batched(len(bufs), moved)
        _tl_xfer_span("d2h_batched", bufs[0].meta, t0, nbytes=moved)
    out: List[TensorBuffer] = []
    for i, b in enumerate(bufs):
        if direct[i] or not any((i, j) in where
                                for j in range(len(b.tensors))):
            out.append(b.to_host())  # cached view or already-host payload
            continue
        host = [t if isinstance(t, np.ndarray)
                else np.asarray(fetched[where[(i, j)]])
                for j, t in enumerate(b.tensors)]
        hb = TensorBuffer(tensors=host, pts=b.pts, dts=b.dts,
                          duration=b.duration, meta=dict(b.meta),
                          finalize=None)
        if b.finalize is not None:
            hb = b.finalize(hb)
        if isinstance(b, DeviceBuffer):
            b._host_cache = hb  # later to_host() callers share this
        out.append(hb)
    return out
