"""DispatchWindow — bounded async dispatch per filter / fused region.

XLA dispatch is asynchronous: ``jitted(...)`` returns device handles
before the device finishes. The pipeline previously consumed that
asynchrony one frame at a time — the next frame's host work only started
once the previous frame's downstream chain returned, and any downstream
materialization point fenced every frame individually, so the device sat
idle between dispatches (the flagship stream once ran at 13.4% of the
device ceiling). The overlap layer's contract instead allows up to
``inflight=K`` device batches outstanding per dispatching element: host
work for frame N+1 proceeds while the device computes frame N, and the
producer thread only blocks (fences the OLDEST outstanding batch) when
the window is full — bounded pipelining, same ordering.

The window also owns the staging-buffer recycle point: a pooled host
array consumed by an H2D transfer (``tensors/pool.py``, carried in
``meta["pool_stash"]``) must not be rewritten while the transfer or the
dispatch reading it is in flight. Fencing entry N proves dispatch N
completed, so its stash is released exactly there. Batched window
uploads (``tensors/buffer.py`` ``upload_many``) extend the same
contract: the single window slab that staged a whole drained run rides
the run's LAST buffer's stash, so the in-order fence releases it only
after every dispatch that read any slot of that upload has completed
(a slot still adopted as a DeviceBuffer host view keeps the slab out
of circulation through the pool's refcount guard regardless).

Instrumented as ``nns_filter_inflight`` (current window occupancy) and
``nns_filter_fence_wait_seconds`` (time spent blocked in each fence —
near-zero means the device finishes before the window fills; large means
the pipeline is device-bound at this element).
"""

from __future__ import annotations

import collections
import time
import weakref
from typing import Any, Deque, List, Optional, Tuple

from nnstreamer_tpu.log import get_logger
from nnstreamer_tpu.obs import timeline as _timeline
from nnstreamer_tpu.pipeline import faults as _faults
from nnstreamer_tpu.tensors.buffer import H2D_EXCLUSIVE_META, is_device_array

log = get_logger("dispatch")

#: meta key carrying pool-owned host staging arrays whose release is
#: deferred to the fence point (set by Queue prefetch-device; a batched
#: window upload additionally parks its shared window slab on the run's
#: last buffer here)
POOL_STASH_META = "pool_stash"


def release_shed_payload(buf) -> None:
    """Release a shed/revoked frame's device payload and pool pins NOW.

    A frame the EDF scheduler sheds (or that admission revokes) never
    reaches a fence, so nothing would release its staged pool slabs or
    drop its freshly-uploaded device tensors until GC happens to find
    the dead wrapper — shed work silently pinning HBM and slab bytes is
    exactly the failure mode the memory budget exists to prevent. Safe
    on any buffer: pops the fence-deferred ``pool_stash`` back to the
    pool, and clears the device tensor list only when the payload is
    marked ``h2d_exclusive`` (an upload point created it for exactly one
    downstream consumer — us — so no other reader exists)."""
    meta = getattr(buf, "meta", None)
    if meta is None or not hasattr(meta, "pop"):
        return
    stash = meta.pop(POOL_STASH_META, None)
    if stash:
        from nnstreamer_tpu.tensors.pool import get_pool

        get_pool().release_many(stash)
    if meta.pop(H2D_EXCLUSIVE_META, None):
        tensors = getattr(buf, "tensors", None)
        if tensors and all(is_device_array(t) for t in tensors):
            tensors.clear()


class DispatchWindow:
    """Per-element window of outstanding (dispatched, unfenced) batches.

    Not thread-safe on its own: a window belongs to one element whose
    chain runs on one streaming thread at a time (the same contract every
    element's ``chain`` already has).
    """

    def __init__(self, owner):
        #: weakly bound: the window must not keep a dead element (and its
        #: pipeline) alive through the metrics registry
        self._owner = weakref.ref(owner)
        self._entries: Deque[
            Tuple[List[Any], Optional[list], Optional[int], float]] = \
            collections.deque()
        self._m_fence = None
        self._m_poisoned = None
        self._gauge_done = False

    def __len__(self) -> int:
        return len(self._entries)

    def _inflight(self) -> int:
        owner = self._owner()
        if owner is None:
            return 1
        try:
            return max(0, int(owner.get_property("inflight")))
        except (KeyError, TypeError, ValueError):
            return 2

    def _obs(self):
        if self._m_fence is None:
            owner = self._owner()
            if owner is None:
                return None
            from nnstreamer_tpu.obs import get_registry

            reg = get_registry()
            labels = owner._obs_labels()
            self._m_fence = reg.histogram(
                "nns_filter_fence_wait_seconds",
                "Time blocked fencing the oldest outstanding dispatch "
                "(window full or EOS)", **labels)
            if not self._gauge_done:
                ref = weakref.ref(self)
                reg.gauge(
                    "nns_filter_inflight",
                    "Dispatched device batches currently outstanding",
                    fn=lambda: (len(ref()) if ref() is not None else 0),
                    **labels)
                self._gauge_done = True
        return self._m_fence

    # -- hot path -----------------------------------------------------------
    def admit(self, tensors: List[Any],
              stash: Optional[list] = None,
              frame: Optional[int] = None) -> None:
        """Register a just-dispatched batch; fence the oldest entries
        until at most ``inflight`` remain outstanding. Accepts a raw
        tensor list or a whole (Device)Buffer — a device-resident input
        arrived with no H2D stage and no pool stash, so its entry is
        purely an ordering fence. ``frame`` is the frame's trace seq so
        the timeline can draw the inflight slot as an async span."""
        tensors = getattr(tensors, "tensors", tensors)
        t_admit = time.monotonic()
        self._entries.append((list(tensors), stash, frame, t_admit))
        tl = _timeline.ACTIVE
        if tl is not None and frame is not None:
            tl.async_begin("inflight", frame, t_admit)
        limit = self._inflight()
        while len(self._entries) > limit:
            self._fence_oldest()

    def _fence_oldest(self) -> None:
        """Fence the oldest outstanding batch. A failing fence (device
        error surfacing at ``block_until_ready``, or an injected
        ``dispatch.fence`` fault) poisons ONLY that batch: the entry is
        already popped, its stash still releases, the timeline still
        closes its inflight span — the entries behind it fence normally
        on later calls, so in-order delivery of the surviving frames is
        never corrupted. The wrapped error propagates to the dispatching
        element's chain, where its error policy decides the outcome."""
        tensors, stash, frame, _t_admit = self._entries.popleft()
        hist = self._obs()
        t0 = time.monotonic()
        err: Optional[BaseException] = None
        try:
            fi = _faults.ACTIVE
            if fi is not None:
                # chaos hook: kind=stall parks this fence (watchdog
                # bait); kind=raise poisons the batch
                fi.check("dispatch.fence", seq=frame)
            for t in tensors:
                if is_device_array(t):
                    t.block_until_ready()
        except Exception as e:  # noqa: BLE001 — isolation: bookkeeping
            # below must run before the poisoned batch's error surfaces
            err = e
        t1 = time.monotonic()
        if hist is not None:
            hist.observe(t1 - t0)
        tl = _timeline.ACTIVE
        if tl is not None and frame is not None:
            tl.span("fence_wait", frame, t0, t1, track="dispatch")
            tl.async_end("inflight", frame, t1)
        if stash:
            # the fenced dispatch (and the H2D feeding it) is complete:
            # its pooled host staging buffers have no readers left —
            # except a stash array adopted as a DeviceBuffer's cached
            # host view, which the pool keeps pinned (release refuses it)
            # until that wrapper dies
            from nnstreamer_tpu.tensors.pool import get_pool

            get_pool().release_many(stash)
        if err is not None:
            self._count_poisoned()
            from nnstreamer_tpu.pipeline.element import FlowError

            owner = self._owner()
            name = owner.name if owner is not None else "dispatch"
            if isinstance(err, FlowError):
                raise err
            raise FlowError(
                f"{name}: poisoned in-flight batch at fence: {err}"
            ) from err

    def _count_poisoned(self) -> None:
        if self._m_poisoned is None:
            from nnstreamer_tpu.obs import get_registry

            owner = self._owner()
            labels = owner._obs_labels() if owner is not None else {}
            self._m_poisoned = get_registry().counter(
                "nns_fault_poisoned_batches_total",
                "In-flight dispatches whose fence failed (batch "
                "isolated; entries behind it fence normally)", **labels)
        self._m_poisoned.inc()

    def drain(self, on_error: str = "raise") -> None:
        """Fence everything outstanding (EOS / stop / unsplice). A
        poisoned batch never strands the entries behind it: every entry
        is fenced (stashes released) and the FIRST failure re-raises at
        the end — or is only logged with ``on_error="log"``, the
        teardown mode where a raise would abort the rest of stop()."""
        first: Optional[BaseException] = None
        while self._entries:
            try:
                self._fence_oldest()
            except Exception as e:  # noqa: BLE001 — keep fencing: the
                # remaining entries' stashes must still release
                if first is None:
                    first = e
        if first is not None:
            if on_error == "log":
                log.warning("dispatch drain: poisoned batch during "
                            "teardown: %s", first)
                return
            raise first

    def snapshot(self) -> dict:
        out = {"inflight_now": len(self._entries),
               "inflight_limit": self._inflight()}
        h = self._m_fence
        if h is not None and h.count:
            out["fence_wait_p50_ms"] = round(
                (h.percentile(50) or 0.0) * 1e3, 3)
            out["fence_wait_p99_ms"] = round(
                (h.percentile(99) or 0.0) * 1e3, 3)
        return out
