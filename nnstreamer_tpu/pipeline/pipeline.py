"""Pipeline — element container, scheduler, and bus.

The reference's pipelines are GStreamer pipelines: sources run streaming
threads, ``queue`` elements decouple stages, a bus carries ERROR/EOS messages
to the application. This module provides the same capability:

- :class:`Pipeline` holds elements, drives state changes
  (NULL→READY→PLAYING, reference state model), runs one thread per source
  element, and exposes a bus (:meth:`pop_message`, :meth:`wait`).
- :class:`SourceElement` is the push-mode live/file source base
  (GstBaseSrc's create-loop, e.g. tensor_src_iio.c:18-52).
- :class:`Queue` is the explicit thread boundary (gst ``queue``): a bounded
  buffer + worker thread giving pipeline (stage) parallelism — the
  reference's only intra-pipeline parallelism form (SURVEY §2.4.1). Stages
  separated by queues overlap host work with XLA's async device dispatch.
"""

from __future__ import annotations

import enum
import heapq
import queue as _queue
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from nnstreamer_tpu.log import get_logger
from nnstreamer_tpu.obs import get_registry, register_pipeline_collector
from nnstreamer_tpu.obs import timeline as _timeline
from nnstreamer_tpu.pipeline import faults as _faults
from nnstreamer_tpu.pipeline.element import (
    Element,
    EosEvent,
    Event,
    FlowError,
    FlowReturn,
    Pad,
)
from nnstreamer_tpu.registry import ELEMENT, subplugin
from nnstreamer_tpu.tensors import memory as _memory
from nnstreamer_tpu.tensors.buffer import TensorBuffer

log = get_logger("pipeline")


class State(enum.Enum):
    NULL = "null"
    READY = "ready"
    PLAYING = "playing"


class Message:
    """Bus message (GstMessage equivalent)."""

    def __init__(self, kind: str, source: Optional[Element] = None,
                 error: Optional[Exception] = None,
                 text: Optional[str] = None):
        self.kind = kind  # "eos" | "error" | "warning"
        self.source = source
        self.error = error
        self.text = text  # human-readable detail (warnings)

    def __repr__(self):
        detail = f", text={self.text!r}" if self.text else ""
        return (f"Message({self.kind}, "
                f"src={getattr(self.source, 'name', None)}, "
                f"err={self.error}{detail})")


class SourceElement(Element):
    """Push-mode source: the pipeline runs :meth:`create` in a loop on a
    dedicated streaming thread until it returns None (EOS) or the pipeline
    stops."""

    ELEMENT_NAME = "source"
    PROPERTIES = {**Element.PROPERTIES}

    def __init__(self, name=None, **props):
        super().__init__(name, **props)
        if not self.srcpads:
            self.add_src_pad("src")
        self._stop_evt = threading.Event()

    def create(self) -> Optional[TensorBuffer]:
        """Produce the next buffer, or None at end-of-stream. Blocking calls
        must poll ``self._stop_evt``."""
        raise NotImplementedError

    def negotiate(self) -> None:
        """Announce src caps before the first buffer (override)."""

    # -- driven by Pipeline ---------------------------------------------------
    def run_loop(self, pipeline: "Pipeline") -> None:
        try:
            self.negotiate()
            while not self._stop_evt.is_set():
                buf = self.create()
                if buf is None:
                    break
                # capture-time stamp for end-to-end frame latency: sinks
                # measure now-create_t at materialization (the reference
                # self-measures exactly this around its hot path,
                # tensor_filter.c:349-423). appsrc callers may pre-set it.
                if "create_t" not in buf.meta:
                    buf.meta["create_t"] = time.monotonic()
                # frame-ledger trace context (obs/timeline.py): one
                # monotone id per frame, stamped by the single source
                # thread — the same single-writer discipline the lane
                # executor uses for its reorder sequence
                tl = _timeline.ACTIVE
                if tl is not None and \
                        _timeline.TRACE_SEQ_META not in buf.meta:
                    buf.meta[_timeline.TRACE_SEQ_META] = tl.next_seq()
                ret = self.srcpad.push(buf)
                if ret is FlowReturn.EOS:
                    break
            for sp in self.srcpads:
                sp.push_event(EosEvent())
            pipeline.post_message(Message("eos", self))
        except FlowError as e:
            pipeline.post_error(self, e)
        except Exception as e:  # noqa: BLE001 — bus carries any failure
            pipeline.post_error(self, e)

    def stop(self):
        self._stop_evt.set()
        super().stop()


@subplugin(ELEMENT, "queue")
class Queue(Element):
    """Thread-boundary element: bounded FIFO + worker thread.

    ``max_size_buffers`` bounds occupancy; ``leaky`` ("no"|"downstream")
    selects blocking vs drop-oldest backpressure (gst queue's leaky prop).
    """

    ELEMENT_NAME = "queue"
    HANDLES_DEFERRED = True  # pure hand-off: finalize stays lazy across it
    DEVICE_PASSTHROUGH = True  # never reads tensor bytes on the host
    PROPERTIES = {**Element.PROPERTIES, "max_size_buffers": 16, "leaky": "no",
                  "prefetch_host": False, "prefetch_device": False,
                  # stamp_admission: record meta["admitted_t"] when a buffer
                  # is accepted into the FIFO. A leaky ingress queue is the
                  # admission-control point of a saturated pipeline: sinks
                  # report latency from this stamp (base="admitted") so the
                  # saturation-phase p99 measures service time of frames the
                  # pipeline actually served, not the unbounded backlog wait
                  # a free-running source builds before the drop point.
                  "stamp_admission": False,
                  # materialize_host: drain in groups and hand HOST buffers
                  # downstream (one overlapped D2H flush per backlog; the
                  # deferred finalize is applied here). For sink-bound
                  # queues feeding to-host consumers; unlike prefetch_host
                  # it changes the payload type, so it is its own opt-in.
                  "materialize_host": False,
                  # batch drain: max buffers the worker gathers per wake
                  # (whatever is ALREADY queued — it never waits). Runs of
                  # data buffers go to HANDLES_LIST peers as one list;
                  # 1 disables gathering entirely.
                  "drain_batch": 64,
                  # batch_h2d: with prefetch_device, defer the upload to
                  # the drain side and coalesce each gathered run into a
                  # single staged multi-frame slab upload (one pool
                  # window slab, one device_put; per-frame views carved
                  # device-side — tensors/buffer.py upload_many). False
                  # restores the per-frame producer-side to_device path.
                  "batch_h2d": True,
                  # slo_budget_ms: per-queue SLO budget (ms). >0 makes
                  # this queue an admission point of the pipeline's
                  # SloScheduler (serving/scheduler.py): deadline
                  # admission at chain(), EDF ordering instead of FIFO,
                  # late-first shedding on overflow, and batch forming
                  # capped by the feedback controller. 0 (default) with
                  # no pipeline-level budget = the exact pre-scheduler
                  # path (no scheduler object is even built).
                  "slo_budget_ms": 0.0}

    _EOS = object()
    #: worker wake token for scheduler mode — data rides the EDF heap,
    #: the FIFO carries only ordering (tokens/events/EOS)
    _TOKEN = object()

    #: rate limit for the leaky-drop warning (seconds between warnings)
    DROP_WARN_INTERVAL_S = 5.0

    def __init__(self, name=None, **props):
        super().__init__(name, **props)
        self.add_sink_pad("sink")
        self.add_src_pad("src")
        self._q: _queue.Queue = _queue.Queue()
        self._worker: Optional[threading.Thread] = None
        self._stop_evt = threading.Event()
        self._eos_done = threading.Event()
        #: serving MeshPlan of the sharded invoker this queue stages
        #: uploads for (set by Pipeline.start() via note_mesh_plan). Not
        #: named _mesh_plan: that attribute marks an INVOKER to fuse.py
        self._upload_plan = None
        self._m_drops = None      # leaky-downstream drop counter (lazy)
        self._m_blocked = None    # cumulative blocked-put seconds (lazy)
        self._m_drain = None      # per-wake drain size histogram (lazy)
        #: data buffers the worker has popped but not yet handed
        #: downstream — batch drain moves the backlog out of the FIFO in
        #: one wake, so qsize() alone would under-report occupancy while
        #: the worker is blocked delivering (single-writer: the worker)
        self._undelivered = 0
        self._last_drop_warn_t = 0.0
        self._drops_since_warn = 0
        #: SLO scheduler binding (serving/scheduler.py), resolved at
        #: start(); None = plain FIFO queue, the kill-switch path
        self._sched = None
        self._budget_ms = 0.0
        self._edf: list = []          # (deadline_t, seq, buf) heap
        self._edf_lock = threading.Lock()
        self._edf_seq = 0             # FIFO tiebreak for equal deadlines
        self._m_admitted = None       # stamp_admission accept counter
        self._m_adm_revoked = None    # admitted-then-dropped counter

    def _obs_init(self):
        """Queue metrics: depth gauge (sampled), drop counter, blocked
        time. Created at start() so the labels carry the owning
        pipeline's name."""
        reg = get_registry()
        labels = self._obs_labels()
        self._m_drops = reg.counter(
            "nns_queue_drops_total",
            "Buffers discarded by leaky=downstream backpressure", **labels)
        self._m_blocked = reg.counter(
            "nns_queue_blocked_seconds_total",
            "Cumulative producer time spent blocked on a full queue",
            **labels)
        self._m_drain = reg.histogram(
            "nns_queue_drain_size",
            "Data buffers the worker drained per wake (backlog batching)",
            buckets=(1, 2, 4, 8, 16, 32, 64), **labels)
        self._m_admitted = reg.counter(
            "nns_queue_admitted_total",
            "Buffers accepted at a stamp_admission point", **labels)
        self._m_adm_revoked = reg.counter(
            "nns_queue_admitted_revoked_total",
            "Admitted buffers later dropped before delivery (the "
            "admitted population nets these out)", **labels)
        import weakref

        ref = weakref.ref(self)
        reg.gauge("nns_queue_depth", "Buffers currently queued",
                  fn=lambda: (ref()._depth() if ref() is not None else 0),
                  **labels)

    def _count_drop(self) -> None:
        """Satellite: leaky-downstream drops were silent — count every
        one and emit one rate-limited warning so live operators see the
        loss without per-frame log spam."""
        self._m_drops.inc()
        self._drops_since_warn += 1
        now = time.monotonic()
        if now - self._last_drop_warn_t >= self.DROP_WARN_INTERVAL_S:
            self.log.warning(
                "%s: leaky=downstream dropped %d buffer(s) since last "
                "report (downstream slower than producer; total %d)",
                self.name, self._drops_since_warn,
                int(self._m_drops.value))
            self._last_drop_warn_t = now
            self._drops_since_warn = 0

    # -- frame-ledger hooks (obs/timeline.py) --------------------------------
    def _tl_arrive(self, buf) -> None:
        """The FIRST queue a frame reaches closes its ingest span
        (source ``create()`` → here, minus any lane reorder wait, so
        ingest + lane_reorder tile exactly); every queue stamps the
        entry time its drain side turns into a queue_wait/sched_hold
        span. No-op (one attr read) with tracing off."""
        tl = _timeline.ACTIVE
        if tl is None:
            return
        seq = buf.meta.get(_timeline.TRACE_SEQ_META)
        if seq is None:
            return
        now = time.monotonic()
        if "tl_ingest_done" not in buf.meta:
            buf.meta["tl_ingest_done"] = True
            create = buf.meta.get("create_t")
            if create is not None:
                reorder = buf.meta.pop("tl_reorder_s", 0.0)
                tl.span("ingest", seq, create,
                        max(now - reorder, create), track="ingest")
        buf.meta["tl_q_t"] = now

    def _tl_depart(self, buf, kind: Optional[str] = None) -> None:
        """Drain-side twin of :meth:`_tl_arrive`: queue residency ends
        when the worker pops the frame. FIFO pops record ``queue_wait``,
        EDF pops ``sched_hold``."""
        tl = _timeline.ACTIVE
        if tl is None:
            return
        t0 = buf.meta.pop("tl_q_t", None)
        if t0 is None:
            return
        seq = buf.meta.get(_timeline.TRACE_SEQ_META)
        if seq is None:
            return
        if kind is None:
            kind = "sched_hold" if self._sched is not None else "queue_wait"
        tl.span(kind, seq, t0, time.monotonic(), track=self.name)

    def _depth(self) -> int:
        """Occupancy: FIFO (or EDF heap in scheduler mode) + popped but
        undelivered."""
        if self._sched is not None:
            with self._edf_lock:
                queued = len(self._edf)
        else:
            queued = self._q.qsize()
        return queued + self._undelivered

    def obs_snapshot(self):
        out = super().obs_snapshot()
        out["depth"] = self._depth()
        if self._m_drops is not None:
            out["drops"] = int(self._m_drops.value)
            out["blocked_s"] = round(self._m_blocked.value, 4)
        if self._m_drain is not None and self._m_drain.count:
            out["drain_size_p50"] = self._m_drain.percentile(50)
        return out

    def start(self):
        super().start()
        self._stop_evt.clear()
        self._eos_done.clear()
        self._undelivered = 0
        # scheduler binding: this queue is an admission point when the
        # pipeline has an SloScheduler AND this queue either stamps
        # admission or carries its own budget. No scheduler (budget
        # unset anywhere) = the exact pre-scheduler FIFO path.
        own_budget = float(self.get_property("slo_budget_ms") or 0.0)
        sched = getattr(self.pipeline, "_slo_scheduler", None)
        if sched is not None and (own_budget > 0
                                  or self.get_property("stamp_admission")):
            self._sched = sched
            self._budget_ms = own_budget if own_budget > 0 \
                else sched.budget_ms
        else:
            self._sched = None
        if self._sched is not None:
            # data rides the EDF heap (bounded by max_size_buffers in
            # _chain_scheduled); the FIFO carries only wake tokens and
            # serialized events, so it must never block a producer
            self._edf = []
            self._edf_seq = 0
            self._q = _queue.Queue()
        else:
            self._q = _queue.Queue(
                maxsize=int(self.get_property("max_size_buffers")))
        if self._m_drops is None:
            self._obs_init()
        self._worker = threading.Thread(
            target=self._drain_sched if self._sched is not None
            else self._drain,
            name=f"{self.name}-worker", daemon=True
        )
        self._worker.start()

    def stop(self):
        self._stop_evt.set()
        try:
            self._q.put_nowait(self._EOS)
        except _queue.Full:
            pass
        if self._worker is not None:
            self._worker.join(timeout=5)
            self._worker = None
        super().stop()

    def accepts_now(self) -> bool:
        """True when a push would be absorbed without blocking/dropping.
        Latency-budget upstreams (aggregator latency-budget-ms) poll
        this before flushing a partial window early: when the pipeline
        is backed up, holding the window (letting it fill toward a full
        batch) beats stacking more dispatches onto a saturated link."""
        if self._worker is None:
            return True
        if self._sched is not None:
            with self._edf_lock:
                return len(self._edf) < \
                    int(self.get_property("max_size_buffers"))
        maxsize = self._q.maxsize
        return maxsize <= 0 or self._q.qsize() < maxsize

    def chain(self, pad, buf):
        fi = _faults.ACTIVE
        if fi is not None:
            # chaos hook (pipeline/faults.py): a raise here surfaces
            # through _chain_entry under THIS queue's error policy
            fi.check("queue.push",
                     seq=buf.meta.get(_timeline.TRACE_SEQ_META))
        if self.get_property("prefetch_host") and \
                not self.get_property("materialize_host"):
            # (materialize_host issues the copies drain-side, grouped)
            # start D2H for device tensors NOW (producer side) so a
            # downstream to_host consumer finds the copy already in flight
            # instead of serializing one device round trip per frame
            for t in buf.tensors:
                start_async = getattr(t, "copy_to_host_async", None)
                if start_async is not None:
                    start_async()
        if self.get_property("prefetch_device"):
            # batch_h2d defers the upload to the drain worker, which
            # coalesces each gathered run into ONE staged window upload
            # (_upload_run); the worker thread still overlaps the
            # transfer with the producer. Producer-side per-frame upload
            # remains for batch_h2d=false and the degenerate unstarted
            # passthrough (no worker to defer to). A frame the SLO
            # scheduler sheds from the EDF heap then never paid its H2D.
            defer = (self.get_property("batch_h2d")
                     and self._worker is not None
                     and not buf.on_device())
            if not defer:
                buf = self._upload_one(buf)
        self._tl_arrive(buf)
        if self._sched is not None and self._worker is not None:
            # SLO path: deadline admission + EDF heap; rejected frames
            # never carry an admission stamp and are dropped here
            return self._chain_scheduled(buf)
        if self.get_property("stamp_admission"):
            if "admitted_t" not in buf.meta:
                buf.meta["admitted_t"] = time.monotonic()
                if self._m_admitted is not None:
                    self._m_admitted.inc()
        if self._worker is None:  # not started: degenerate passthrough
            return self.srcpad.push(buf)
        if self.get_property("leaky") == "downstream":
            while True:
                try:
                    self._q.put_nowait(buf)
                    return FlowReturn.OK
                except _queue.Full:
                    try:
                        dropped = self._q.get_nowait()  # drop oldest
                        self._count_drop()
                        # a frame dropped AFTER stamp_admission leaves
                        # the admitted population: revoke the stamp (a
                        # shared-meta consumer — tee branch, aggregated
                        # window — must not report it as a served-latency
                        # outlier) and count the revocation so admitted
                        # accounting nets out
                        if not (dropped is self._EOS
                                or isinstance(dropped, Event)):
                            if dropped.meta.pop("admitted_t",
                                                None) is not None:
                                self._m_adm_revoked.inc()
                            # the dropped frame never reaches a fence:
                            # release its staged pool slabs / exclusive
                            # device payload now, not at GC
                            from nnstreamer_tpu.pipeline.dispatch import (
                                release_shed_payload,
                            )

                            release_shed_payload(dropped)
                    except _queue.Empty:
                        pass
        else:
            t0 = None
            while not self._stop_evt.is_set():
                try:
                    self._q.put(buf, timeout=0.1)
                    if t0 is not None:
                        self._m_blocked.inc(time.monotonic() - t0)
                    return FlowReturn.OK
                except _queue.Full:
                    if t0 is None:
                        t0 = time.monotonic()
                    continue
            return FlowReturn.EOS

    def sink_event(self, pad, event):
        if self._worker is None:
            super().sink_event(pad, event)
            return
        if isinstance(event, EosEvent):
            # EOS is serialized: enqueue the sentinel in-order, then block
            # until the worker has drained everything ahead of it and
            # forwarded EOS downstream (gst serialized-event semantics).
            self._q.put(self._EOS)
            self._eos_done.wait(timeout=30)
        else:
            # all other events are serialized with the data flow too —
            # a CapsEvent must not overtake buffers queued ahead of it
            self._q.put(event)

    def note_mesh_plan(self, plan) -> None:
        """The invoker downstream of this queue runs under the serving
        mesh ``plan`` (or under none): ``prefetch-device`` uploads then
        land batch-sharded on that mesh straight from the host. Staging
        them on the default device instead makes the sharded region
        re-place every frame chip 0 → mesh, which
        ``nns_reshard_bytes_total`` counts as the mismatch it is. Called
        by Pipeline.start() once every region holds its plan
        (pipeline/fuse.py announce_mesh_upstream)."""
        self._upload_plan = plan

    # -- drain-side H2D batching (tensors/buffer.py upload_many) -------------
    def _upload_one(self, buf):
        """Per-frame upload path (producer-side prefetch, window
        singletons, deferred-pad partial windows): to_device + pool
        stash stamp + DeviceBuffer wrap with the pre-upload host view."""
        if not buf.on_device():
            from nnstreamer_tpu.tensors.buffer import as_device_buffer
            from nnstreamer_tpu.tensors.pool import get_pool

            stash = [t for t in buf.tensors if get_pool().owns(t)]
            host_src = list(buf.tensors)
            plan = self._upload_plan
            buf = buf.to_device(
                sharding=plan.sharding_for if plan is not None else None)
            # the uploaded copy is the payload from here on; the
            # pre-upload host arrays become the wrapper's zero-copy
            # host view (a later to_host costs nothing), and any
            # pool-owned ones are pinned against explicit release
            buf = as_device_buffer(buf, host_view=host_src)
            # freshly uploaded copy with exactly one downstream consumer:
            # a fused region may donate it to XLA (tensors/buffer.py)
            from nnstreamer_tpu.tensors.buffer import H2D_EXCLUSIVE_META

            buf.meta[H2D_EXCLUSIVE_META] = True
            if stash:
                # pooled staging arrays must survive until the
                # dispatch that consumes the uploaded copies has
                # fenced (the H2D may alias or still be in flight);
                # the downstream DispatchWindow releases them at its
                # fence point (pipeline/dispatch.py). to_device()
                # returned a fresh buffer, so its meta is still ours
                # to stamp.
                from nnstreamer_tpu.pipeline.dispatch import POOL_STASH_META

                buf.meta[POOL_STASH_META] = stash
        # a latency-budget partial window deferred its padding here
        # (aggregator pad-device): only the real frames crossed the
        # link; the zero rows are synthesized on device now
        if buf.meta.get("pad_rows"):
            buf = buf.pad_rows_device()
        return buf

    def _upload_group(self, group: list) -> list:
        """One staged multi-frame slab upload for ≥2 same-signature host
        buffers. Per-buffer pool stashes are preserved; the window slabs
        the upload staged through ride the LAST buffer's stash — the
        dispatch window fences in order, so by the time the last frame's
        fence releases them every dispatch that read the upload has
        completed (live DeviceBuffer host views keep their slab out of
        circulation via the pool's refcount guard regardless)."""
        from nnstreamer_tpu.pipeline.dispatch import POOL_STASH_META
        from nnstreamer_tpu.tensors.buffer import upload_many
        from nnstreamer_tpu.tensors.pool import get_pool

        pool = get_pool()
        stashes = [[t for t in b.tensors if pool.owns(t)] for b in group]
        devs, slabs = upload_many(group)
        for b, st in zip(devs, stashes):
            if st:
                b.meta[POOL_STASH_META] = st
        if slabs:
            last = devs[-1]
            last.meta[POOL_STASH_META] = list(
                last.meta.get(POOL_STASH_META) or []) + slabs
        return devs

    def _upload_run(self, run: list) -> list:
        """Split a drained run into maximal groups of consecutive
        host-resident, identically-shaped buffers and upload each group
        as one window slab; singletons, device-resident buffers, and
        deferred-pad partials take the per-frame path."""
        import numpy as _np

        # under a mesh plan every buffer uploads on its own, scattered
        # over the mesh by _upload_one: a window slab would land on one
        # device and its per-frame slices with it
        meshed = self._upload_plan is not None

        def _single(b) -> bool:
            return (meshed or b.on_device() or not b.tensors
                    or b.meta.get("pad_rows")
                    or not all(isinstance(t, _np.ndarray)
                               for t in b.tensors))

        out: list = []
        i = 0
        while i < len(run):
            b = run[i]
            if _single(b):
                out.append(self._upload_one(b))
                i += 1
                continue
            sig = [(t.shape, t.dtype) for t in b.tensors]
            j = i + 1
            while j < len(run) and not _single(run[j]) and \
                    [(t.shape, t.dtype)
                     for t in run[j].tensors] == sig:
                j += 1
            if j - i >= 2:
                out.extend(self._upload_group(run[i:j]))
            else:
                out.append(self._upload_one(b))
            i = j
        return out

    def _flush_run(self, run: list) -> None:
        """Deliver a gathered run of data buffers: materialized one by
        one (materialize_host), as ONE list hand-off when the peer opts
        in (``Pad.push_list`` → ``HANDLES_LIST``), else per-buffer."""
        if not run:
            return
        if self.get_property("prefetch_device") and \
                self.get_property("batch_h2d"):
            # deferred uploads land here: the whole run crosses H2D as
            # one staged slab (buffer identity changes; the timeline/
            # admission meta rides along on the uploaded copies)
            run = self._upload_run(run)
        # queue-residency spans end HERE, per item, right before its
        # hand-off — stamping at drain-pop time would hide the in-batch
        # wait (item N sitting in the drained run while items 0..N-1
        # push through the downstream chain) as unattributed e2e time
        tl_on = _timeline.ACTIVE is not None
        if self.get_property("materialize_host"):
            # materialize HERE, where the group's copies were just
            # issued — handing device arrays onward would re-serialize
            # the fetches at the sink. The whole run comes back in ONE
            # grouped device_get (zero per-frame D2H round trips —
            # d2h_per_frame stays 0 on a device-decodable pipeline);
            # per-buffer finalize/caching semantics match to_host().
            from nnstreamer_tpu.tensors.buffer import materialize_many

            hosts = materialize_many(run)
            for it, host in zip(run, hosts):
                self._undelivered -= 1
                if tl_on:
                    self._tl_depart(it)
                self.srcpad.push(host)
        elif len(run) > 1:
            peer = self.srcpad.peer
            if peer is not None and getattr(peer.element,
                                            "HANDLES_LIST", False):
                # one chain_list hand-off: the whole run leaves at once
                self._undelivered -= len(run)
                if tl_on:
                    for it in run:
                        self._tl_depart(it)
                self.srcpad.push_list(run)
            else:
                # push_list would fall back to sequential pushes — keep
                # the occupancy honest while the peer works through them
                for it in run:
                    self._undelivered -= 1
                    if tl_on:
                        self._tl_depart(it)
                    self.srcpad.push(it)
        else:
            self._undelivered -= 1
            if tl_on:
                self._tl_depart(run[0])
            self.srcpad.push(run[0])

    def _drain(self):
        group_host = bool(self.get_property("materialize_host"))
        drain_max = max(1, int(self.get_property("drain_batch")))
        while not self._stop_evt.is_set():
            try:
                item = self._q.get(timeout=0.1)
            except _queue.Empty:
                continue
            batch = [item]
            if drain_max > 1 and not isinstance(item, Event) and \
                    item is not self._EOS:
                # gather whatever is ALREADY queued (never wait): one
                # grouped flush services the whole backlog — one worker
                # wake, one downstream hand-off. A blocking fetch costs
                # a full host↔device round trip no matter the size, but
                # transfers started from this thread right before the
                # block all ride the same round.
                while len(batch) < drain_max:
                    try:
                        nxt = self._q.get_nowait()
                    except _queue.Empty:
                        break
                    batch.append(nxt)
                    if nxt is self._EOS or isinstance(nxt, Event):
                        break  # events stay serialized with the data flow
            ndata = sum(1 for it in batch
                        if it is not self._EOS and not isinstance(it, Event))
            self._undelivered += ndata
            if ndata and self._m_drain is not None:
                self._m_drain.observe(ndata)
            if group_host:
                for it in batch:
                    if isinstance(it, Event) or it is self._EOS:
                        continue
                    for t in it.tensors:
                        start_async = getattr(t, "copy_to_host_async", None)
                        if start_async is not None:
                            start_async()
            run: list = []
            try:
                for it in batch:
                    if it is self._EOS or isinstance(it, Event):
                        # events delimit runs and stay serialized: drain
                        # the data queued ahead of them first
                        self._flush_run(run)
                        run = []
                        if it is self._EOS:
                            self.srcpad.push_event(EosEvent())
                            self._eos_done.set()
                            return
                        self.srcpad.push_event(it)
                    else:
                        run.append(it)
                self._flush_run(run)
            except Exception as e:  # noqa: BLE001 — downstream
                # negotiation or chain failures must reach the bus,
                # not silently kill this worker thread
                self.post_error(e if isinstance(e, FlowError)
                                else FlowError(f"{self.name}: {e}"))
                self._eos_done.set()  # unblock a waiting EOS pusher
                return

    # -- SLO scheduler mode (serving/scheduler.py) ---------------------------
    def _chain_scheduled(self, buf) -> FlowReturn:
        """Producer side of scheduler mode: deadline admission, EDF
        enqueue, late-first shedding on overflow. With a uniform budget
        deadlines are monotone in arrival order, so an unloaded queue's
        pop order equals FIFO — byte-identical output."""
        sched = self._sched
        now = time.monotonic()
        with self._edf_lock:
            backlog = len(self._edf) + self._undelivered
        if not sched.admit(buf, now=now, backlog=backlog,
                           budget_ms=self._budget_ms):
            self._count_drop()
            return FlowReturn.OK  # rejected at the door, never admitted
        if self._m_admitted is not None:
            self._m_admitted.inc()
        cap = int(self.get_property("max_size_buffers"))
        shed = None
        with self._edf_lock:
            self._edf_seq += 1
            heapq.heappush(self._edf,
                           (buf.meta["deadline_t"], self._edf_seq, buf))
            if cap > 0 and len(self._edf) > cap:
                shed = self._shed_one_locked(now)
        if shed is not None:
            sched.note_shed(shed, now)
            self._m_adm_revoked.inc()
            self._count_drop()
        self._q.put_nowait(self._TOKEN)  # wake the worker (unbounded)
        return FlowReturn.OK

    def _shed_one_locked(self, now: float):
        """Pick the overflow victim (caller holds ``_edf_lock``):
        late-first — the MOST-late frame (earliest past deadline, i.e.
        the heap root) sheds before any on-time one; with nothing late
        yet, the least-urgent (latest-deadline) frame goes."""
        if self._edf[0][0] <= now:
            return heapq.heappop(self._edf)[2]
        i = max(range(len(self._edf)), key=lambda j: self._edf[j][0])
        victim = self._edf[i][2]
        last = self._edf.pop()
        if i < len(self._edf):
            self._edf[i] = last
            heapq.heapify(self._edf)
        return victim

    def _flush_edf(self, limit: Optional[int],
                   group_host: bool) -> None:
        """Batch former: pop up to ``limit`` admitted frames in EDF
        order and deliver them as one run (``push_list`` to
        HANDLES_LIST peers — the downstream DispatchWindow's fence is
        the free-slot backpressure: a full window blocks this worker, so
        new batches only form when a dispatch slot frees).

        Frames whose deadline passed while they sat in the heap are
        shed HERE, not delivered: serving them would burn device time on
        work that already missed its SLO and then report the miss as an
        admitted-latency outlier (the EOS flush after a stall was the
        worst case: every parked frame surfaced at once, hundreds of ms
        late). On the sequential hand-off path the deadline is re-tested
        per frame right before its push — a stall INSIDE the run (a slow
        peer, GIL contention) makes frames that were on time when the
        batch formed go late while they wait behind it. A HANDLES_LIST
        peer gets the whole run in one hand-off instead: the frames
        become in-flight together, so there is no serial wait to re-test
        for. An unloaded pipeline never goes late, so the byte-
        identical-to-FIFO contract is untouched."""
        now = time.monotonic()
        shed: list = []
        with self._edf_lock:
            n = len(self._edf) if limit is None \
                else min(max(1, limit), len(self._edf))
            run = []
            while self._edf and len(run) < n:
                deadline_t, _seq, buf = heapq.heappop(self._edf)
                if deadline_t <= now:
                    shed.append(buf)
                else:
                    run.append(buf)
        if run:
            self._undelivered += len(run)
            if self._m_drain is not None:
                self._m_drain.observe(len(run))
            if group_host:
                for it in run:
                    for t in it.tensors:
                        start_async = getattr(t, "copy_to_host_async",
                                              None)
                        if start_async is not None:
                            start_async()
            peer = self.srcpad.peer
            if len(run) > 1 and not group_host and peer is not None \
                    and getattr(peer.element, "HANDLES_LIST", False):
                self._flush_run(run)
            else:
                for it in run:
                    if it.meta["deadline_t"] <= time.monotonic():
                        self._undelivered -= 1
                        shed.append(it)
                        continue
                    self._flush_run([it])
        for buf in shed:
            self._sched.note_shed(buf, time.monotonic())
            self._m_adm_revoked.inc()
            self._count_drop()

    def _drain_sched(self):
        """Scheduler-mode worker: wake tokens pop EDF batches capped by
        the feedback controller; events/EOS flush all pending data first
        (EDF order) so serialized-event semantics hold — an event never
        overtakes data queued ahead of it."""
        group_host = bool(self.get_property("materialize_host"))
        sched = self._sched
        while not self._stop_evt.is_set():
            try:
                item = self._q.get(timeout=0.1)
            except _queue.Empty:
                continue
            try:
                if item is self._EOS or isinstance(item, Event):
                    self._flush_edf(None, group_host)
                    if item is self._EOS:
                        self.srcpad.push_event(EosEvent())
                        self._eos_done.set()
                        return
                    self.srcpad.push_event(item)
                else:
                    # a shed frame leaves its wake token behind — the
                    # token then pops an empty heap, a cheap no-op
                    self._flush_edf(sched.batch_cap(), group_host)
            except Exception as e:  # noqa: BLE001 — downstream failures
                # must reach the bus, not silently kill this worker
                self.post_error(e if isinstance(e, FlowError)
                                else FlowError(f"{self.name}: {e}"))
                self._eos_done.set()
                return


class Pipeline:
    """Element container + scheduler + bus."""

    def __init__(self, name: str = "pipeline", fuse: bool = True,
                 lanes: int = 1, slo_budget_ms: float = 0.0,
                 error_policy: Optional[str] = None,
                 watchdog_s: float = 0.0):
        self.name = name
        self.elements: List[Element] = []
        self.by_name: Dict[str, Element] = {}
        self.state = State.NULL
        self._bus: _queue.Queue = _queue.Queue()
        self._threads: List[threading.Thread] = []
        self._eos_pending = 0
        self._lock = threading.Lock()
        self._fuse = fuse
        self._regions: Optional[list] = None
        #: requested ingest lane count (pipeline/lanes.py); 1 = serial
        #: path, NNSTPU_LANES env overrides at start time
        self.lanes = lanes
        self._lane_execs: Optional[list] = None
        #: pipeline-wide SLO budget in ms (serving/scheduler.py); >0
        #: activates deadline admission + EDF + feedback control on the
        #: admission-point queues at start(). 0/unset = no scheduler
        #: object at all — the byte-identical pre-scheduler path.
        self.slo_budget_ms = float(slo_budget_ms or 0.0)
        self._slo_scheduler = None
        #: pipeline-default error policy (pipeline/supervise.py);
        #: elements without their own ``error-policy`` property inherit
        #: this. None = ``halt``, the historical fail-fast behavior.
        self.error_policy = error_policy
        #: watchdog deadline in seconds (>0 arms PipelineWatchdog at
        #: start()); NNSTPU_WATCHDOG_S overrides when unset
        self.watchdog_s = float(watchdog_s or 0.0)
        self._watchdog = None
        #: tail-event dump directory for the flight recorder
        #: (obs/flight.py); None defers to NNSTPU_FLIGHT. The recorder
        #: itself is always on unless NNSTPU_FLIGHT=0.
        self.flight_dir: Optional[str] = None
        self._flight = None
        #: serving-continuity checkpoint directory
        #: (pipeline/continuity.py); None defers to NNSTPU_CHECKPOINT.
        #: Unset ⇒ the continuity layer never runs (exact kill switch).
        self.checkpoint_dir: Optional[str] = None
        self._continuity_restored = False
        # export per-element latency/throughput gauges at scrape time
        # (weakref-bound: a collected pipeline unregisters itself)
        register_pipeline_collector(self)

    # -- construction ---------------------------------------------------------
    def add(self, *elements: Element) -> "Pipeline":
        for el in elements:
            if el.name in self.by_name:
                raise ValueError(f"duplicate element name {el.name!r}")
            el.pipeline = self
            self.elements.append(el)
            self.by_name[el.name] = el
        return self

    def add_linked(self, *elements: Element) -> "Pipeline":
        """Add elements and link them in sequence."""
        self.add(*elements)
        for a, b in zip(elements, elements[1:]):
            a.link(b)
        return self

    def get(self, name: str) -> Element:
        return self.by_name[name]

    def verify(self):
        """Static pre-flight of the constructed graph (no buffers run):
        dangling pads, cycles, sync-policy conflicts, tee fan-out without
        queues. Returns a list of ``analysis.Diagnostic`` — empty when
        the graph is clean. See docs/linting.md for the codes."""
        from nnstreamer_tpu.analysis.verify import verify_pipeline

        return verify_pipeline(self)

    def to_dot(self) -> str:
        """Graphviz dot text of the current runtime graph (fused regions
        as clusters) — pipeline/dot.py."""
        from nnstreamer_tpu.pipeline.dot import pipeline_to_dot

        return pipeline_to_dot(self)

    def metrics_snapshot(self) -> Dict[str, Any]:
        """In-process structured metrics read: one dict per element with
        the reference-style windowed stats (same ``InvokeStats`` the
        ``latency``/``throughput`` properties read) plus element-specific
        extras (queue depth/drops, rate drops/duplicates, sink e2e
        percentiles). The HTTP exporter serves the registry-wide view;
        this is the pipeline-scoped one."""
        elements: Dict[str, Any] = {}
        for el in self.elements:
            stats = el._metrics_stats()
            entry: Dict[str, Any] = {
                "type": el.ELEMENT_NAME,
                "latency_us": stats.latency_us,
                "throughput_milli": stats.throughput_milli,
                "invokes": stats.total_invokes,
            }
            entry.update(el.obs_snapshot())
            elements[el.name] = entry
        out = {"pipeline": self.name, "state": self.state.value,
               "elements": elements}
        from nnstreamer_tpu.tensors.pool import get_pool, pool_enabled

        if pool_enabled():
            # the ingest staging pool is process-wide (sources/converters/
            # aggregators share it); surfaced here so one snapshot answers
            # "is the hot path recycling or allocating?"
            out["pool"] = get_pool().snapshot()
        if self._lane_execs:
            # lane executors are spliced, not in self.elements — surface
            # them the way fused regions surface through element stats
            out["lanes"] = {ex.name: ex.obs_snapshot()
                            for ex in self._lane_execs}
        if self._slo_scheduler is not None:
            out["scheduler"] = self._slo_scheduler.snapshot()
        if _memory.ACTIVE is not None:
            out["memory"] = _memory.ACTIVE.snapshot()
        if self._flight is not None:
            # always-on flight recorder (obs/flight.py): streaming
            # stage/e2e quantiles + burn rates, and the continuous
            # variance-attribution report
            out["slo"] = self._flight.slo_snapshot()
            out["attribution"] = self._flight.attribution()
            # raw P² marker states per stage — what fleet federation
            # marker-merges into fleet-level quantiles (obs/distributed)
            out["quantiles"] = self._flight.quantile_states()
        return out

    # -- serving continuity (pipeline/continuity.py) ---------------------------
    def swap_model(self, filter_name: str, model: Optional[str] = None,
                   weights: Any = None) -> Dict[str, Any]:
        """Zero-downtime versioned model swap on a running pipeline:
        drain the owning dispatch window (the cutover fence), install
        the new model/weights under a bumped epoch, invalidate the
        owning fused region exactly once. No frames are dropped and
        output is byte-identical up to the cutover seq."""
        from nnstreamer_tpu.pipeline import continuity as _continuity

        return _continuity.swap_model(self, filter_name, model=model,
                                      weights=weights)

    def checkpoint(self, directory: Optional[str] = None) -> str:
        """Serialize the durable serving state (repo slots, scheduler
        EWMAs/knobs, residency LRU order, flight-recorder quantiles,
        query-server dedup windows) into ``directory`` — defaults to
        ``checkpoint_dir`` / ``NNSTPU_CHECKPOINT``."""
        from nnstreamer_tpu.pipeline import continuity as _continuity

        return _continuity.checkpoint(self, directory)

    def restore(self, directory: Optional[str] = None) -> Dict[str, Any]:
        """Re-arm the warm serving state from a checkpoint written by
        :meth:`checkpoint` (typically in a previous process)."""
        from nnstreamer_tpu.pipeline import continuity as _continuity

        return _continuity.restore(self, directory)

    # -- state ----------------------------------------------------------------
    def start(self) -> "Pipeline":
        """NULL→PLAYING: start all elements (non-sources first so queues and
        filters are ready), then spawn one streaming thread per source."""
        if self.state is State.PLAYING:
            return self
        # frame-ledger tracing (obs/timeline.py): honor NNSTPU_TRACE
        # before any element starts so the source stamp and every
        # instrumentation point see the active timeline. Unset env and
        # no explicit activation = ACTIVE stays None and every trace
        # site is a single is-None test.
        _timeline.maybe_activate_env()
        # fault injection (pipeline/faults.py): same discipline —
        # NNSTPU_FAULTS unset leaves faults.ACTIVE None and every hook
        # is one attribute read on the byte-identical path
        _faults.maybe_activate_env()
        # HBM budget accountant (tensors/memory.py): same kill switch —
        # NNSTPU_HBM_BUDGET unset leaves memory.ACTIVE None and no
        # accounting hook anywhere ever fires
        _memory.maybe_activate_env()
        # persistent compile cache (pipeline/continuity.py): must arm
        # before any backend open() can jit — JAX_COMPILATION_CACHE_DIR
        # (or an armed checkpoint dir) unset leaves this at two env reads
        from nnstreamer_tpu.pipeline import continuity as _continuity

        _continuity.maybe_arm_compile_cache(self)
        sources = [e for e in self.elements if isinstance(e, SourceElement)]
        others = [e for e in self.elements if not isinstance(e, SourceElement)]
        # SLO scheduler before any element starts: admission-point
        # queues bind to it in their start(). The budget check runs
        # before the import so the default (no budget anywhere) path
        # never even loads the serving package.
        if self._slo_scheduler is None and (
                self.slo_budget_ms > 0
                or any(float(el._props.get("slo_budget_ms") or 0.0) > 0
                       for el in self.elements)):
            from nnstreamer_tpu.serving.scheduler import ensure_scheduler

            ensure_scheduler(self)
        # always-on flight recorder (obs/flight.py): installed after the
        # scheduler (so the SLO budget is known) and only when no
        # explicit/env timeline already owns the ledger slot. The
        # recorder rides the existing span sites; NNSTPU_FLIGHT=0 keeps
        # ACTIVE None and the off path exactly as before.
        from nnstreamer_tpu.obs import flight as _flight

        fr = _flight.maybe_install(self)
        if fr is not None:
            self._flight = fr
        for el in others:
            el.start()
        # region fusion after backends opened, before any buffer flows
        # (pipeline/fuse.py); splices persist across restarts
        from nnstreamer_tpu.pipeline.fuse import fuse_pipeline, fusion_enabled

        if self._fuse and fusion_enabled() and self._regions is None:
            self._regions = fuse_pipeline(self)
        for r in self._regions or ():
            r.start()
        # mesh-sharded serving plane (parallel/serve.py): verify the
        # matched-sharding contract across device-passthrough boundaries
        # now that every region/backend holds its plan — a mismatch is a
        # hard MeshShardingError HERE, before any frame could silently
        # reshard; then align the SLO scheduler's admission quantum to
        # the dp fan-out so admitted micro-batches split evenly. Both
        # are no-ops without a mesh= property (or with NNSTPU_MESH=0).
        from nnstreamer_tpu.pipeline.fuse import (
            announce_mesh_upstream,
            pipeline_shard_count,
            verify_mesh_boundaries,
        )

        verify_mesh_boundaries(self)
        announce_mesh_upstream(self)
        mesh_quantum = pipeline_shard_count(self)
        if self._slo_scheduler is not None:
            self._slo_scheduler.note_mesh(mesh_quantum)
        if mesh_quantum > 1:
            # mesh-wide batch forming: batch formers (tensor_aggregator
            # — the element the query server pipeline batches through)
            # round their window up to the dp fan-out so formed batches
            # split evenly across the mesh
            for el in self.elements:
                hook = getattr(el, "note_mesh_quantum", None)
                if hook is not None:
                    hook(mesh_quantum)
        # ingest lane splicing after fusion (pipeline/lanes.py): a
        # transform folded into a region is already out of the replicable
        # segment, so its math runs device-side while lanes parallelize
        # what host work remains; splices persist across restarts
        from nnstreamer_tpu.pipeline.lanes import effective_lanes, splice_lanes

        if self._lane_execs is None:
            self._lane_execs = splice_lanes(self, effective_lanes(self.lanes))
        for ex in self._lane_execs:
            ex.start()
        # serving-continuity restore (pipeline/continuity.py): after the
        # scheduler / flight recorder / residency units exist, before the
        # first frame flows — so the warm state is in place for frame 0
        _continuity.maybe_restore_env(self)
        for el in sources:
            el.start()
        self.state = State.PLAYING
        # GST_DEBUG_DUMP_DOT_DIR equivalent (pipeline/dot.py) — after
        # fusion so the dump shows the regions that will actually run
        from nnstreamer_tpu.pipeline.dot import maybe_dump_dot

        maybe_dump_dot(self)
        self._eos_pending = len(sources)
        for src in sources:
            t = threading.Thread(
                target=src.run_loop, args=(self,),
                name=f"{self.name}:{src.name}", daemon=True
            )
            self._threads.append(t)
            t.start()
        # liveness watchdog (pipeline/supervise.py): armed only with an
        # explicit deadline (Pipeline(watchdog_s=) / NNSTPU_WATCHDOG_S)
        # — default off, zero extra threads
        wd_s = self._effective_watchdog_s()
        if wd_s > 0 and self._watchdog is None:
            from nnstreamer_tpu.pipeline.supervise import PipelineWatchdog

            self._watchdog = PipelineWatchdog(self, wd_s)
            self._watchdog.start()
        return self

    def _effective_watchdog_s(self) -> float:
        if self.watchdog_s > 0:
            return self.watchdog_s
        import os

        raw = os.environ.get("NNSTPU_WATCHDOG_S", "").strip()
        if not raw:
            return 0.0
        try:
            return float(raw)
        except ValueError:
            log.warning("NNSTPU_WATCHDOG_S=%r is not a number; watchdog "
                        "stays off", raw)
            return 0.0

    def stop(self) -> "Pipeline":
        if self.state is State.NULL:
            return self
        # watchdog first: teardown quiescence must not read as a stall
        if self._watchdog is not None:
            self._watchdog.stop()
            self._watchdog = None
        for el in self.elements:
            if isinstance(el, SourceElement):
                el.stop()
        for t in self._threads:
            t.join(timeout=10)
        self._threads.clear()
        # lane executors stop after the source threads (their upstream)
        # are parked and before the elements they feed shut down
        for ex in self._lane_execs or ():
            ex.stop()
        for el in self.elements:
            if not isinstance(el, SourceElement):
                el.stop()
        for r in self._regions or ():
            r.stop()
        # drop every staging arena's free slabs (shared ingest pool +
        # per-lane pools): a stopped pipeline must not pin peak-rate
        # slab bytes for the life of the process (nns_pool_bytes_held
        # returns to the outstanding working set)
        from nnstreamer_tpu.tensors.pool import release_all_pools

        release_all_pools()
        self.state = State.NULL
        # serving-continuity checkpoint (pipeline/continuity.py): every
        # element is stopped and every dispatch window drained, so the
        # serialized state is consistent. Unarmed ⇒ one env read.
        from nnstreamer_tpu.pipeline import continuity as _continuity

        _continuity.maybe_checkpoint_on_stop(self)
        # retire the flight recorder before the env-owned export check:
        # a pending tail dump near EOS flushes here, and the recorder
        # object stays on self._flight for the post-EOS footer / bench
        if self._flight is not None:
            from nnstreamer_tpu.obs import flight as _flight

            _flight.retire(self._flight)
        # an env-owned timeline (NNSTPU_TRACE=<path>) exports its ledger
        # once the run is over; explicitly installed timelines are the
        # caller's to export
        _timeline.maybe_export_env()
        return self

    # -- bus ------------------------------------------------------------------
    def post_message(self, msg: Message) -> None:
        self._bus.put(msg)

    def post_error(self, source: Element, error: Exception) -> None:
        log.error("pipeline %s: error from %s: %s", self.name,
                  source.name if source else "?", error)
        self._bus.put(Message("error", source, error))

    def post_warning(self, source: Optional[Element], text: str) -> None:
        """Non-fatal bus message: logged, delivered to ``pop_message``
        readers, and skipped over by ``wait()`` (the pipeline keeps
        running — the reference's GST_MESSAGE_WARNING semantics)."""
        log.warning("pipeline %s: warning from %s: %s", self.name,
                    source.name if source else "?", text)
        self._bus.put(Message("warning", source, text=text))

    def pop_message(self, timeout: Optional[float] = None) -> Optional[Message]:
        try:
            return self._bus.get(timeout=timeout)
        except _queue.Empty:
            return None

    def wait(self, timeout: Optional[float] = None) -> Optional[Message]:
        """Block until every source reached EOS (returns the final EOS
        message) or any element errored (returns the error message)."""
        remaining = self._eos_pending
        deadline = None if timeout is None else (
            threading.TIMEOUT_MAX if timeout < 0 else timeout
        )
        import time

        t_end = None if deadline is None else time.monotonic() + deadline
        while True:
            t_left = None if t_end is None else max(0.0, t_end - time.monotonic())
            msg = self.pop_message(timeout=t_left)
            if msg is None:
                return None  # timed out
            if msg.kind == "error":
                return msg
            if msg.kind == "eos":
                remaining -= 1
                if remaining <= 0:
                    return msg

    def run(self, timeout: Optional[float] = None) -> Optional[Message]:
        """start() + wait() + stop(); raises on error message."""
        self.start()
        try:
            msg = self.wait(timeout=timeout)
            if msg is not None and msg.kind == "error":
                raise FlowError(str(msg.error)) from msg.error
            return msg
        finally:
            self.stop()
