"""tensor_aggregator — temporal frame aggregation / dis-aggregation.

Reference: ``gst/nnstreamer/elements/gsttensoraggregator.c`` (1081 LoC,
tensor_aggregator/README.md): collects ``frames-in`` frames per input
buffer, emits ``frames-out`` frames per output, advancing by
``frames-flush`` (sliding window when flush < out), concatenating along
``frames-dim``. This is the stream-side micro-batching / sequence-window
primitive (SURVEY §2.4.3) — e.g. windowing audio for a sequence model.

``latency-budget-ms`` adds latency-budget adaptive batching on top: a
window that would otherwise hold frames past the budget waiting to fill
is flushed EARLY, padded to ``frames-out`` by repeating the last frame so
the downstream jitted program keeps its single compiled shape (no
per-partial-size recompiles). The padded output carries
``meta["valid_frames"]=k``; ``tensor_sink`` slices the padding off at
materialization and latency stamps cover only the real frames. This is
the per-frame-latency half of the north-star metric: the reference's
per-frame path (tensor_filter.c:349-423) never batches, so its p50 is
one service time — budget mode bounds the admission wait a micro-batched
stream adds while keeping the batched throughput path intact (full
windows are never padded, and a saturated stream fills windows faster
than any budget fires).
"""

from __future__ import annotations

import threading
import time
from typing import List, Optional

import numpy as np

from nnstreamer_tpu.log import get_logger
from nnstreamer_tpu.obs import timeline as _timeline
from nnstreamer_tpu.pipeline.element import Element
from nnstreamer_tpu.registry import ELEMENT, subplugin
from nnstreamer_tpu.tensors.buffer import TensorBuffer, is_device_array

log = get_logger("elements.aggregator")


@subplugin(ELEMENT, "tensor_aggregator")
class TensorAggregator(Element):
    ELEMENT_NAME = "tensor_aggregator"
    #: batch-drain opt-in: a queue backlog arrives as one list, windowed
    #: under ONE lock acquisition (see chain_list)
    HANDLES_LIST = True
    DEVICE_PASSTHROUGH = True  # device windows concat via jnp, host via np
    PROPERTIES = {
        **Element.PROPERTIES,
        "frames_in": 1,
        "frames_out": 1,
        "frames_flush": 0,   # 0 → == frames_out (no overlap)
        "frames_dim": 0,     # innermost-first dim index to aggregate along
        "concat": True,
        # >0: flush a PARTIAL window (padded to frames-out, with
        # meta["valid_frames"]) once the oldest queued frame has waited
        # this many ms — latency-budget adaptive batching. A budget
        # flush emits everything queued (sliding-window overlap does not
        # apply to it) and the remaining tail is flushed at EOS.
        "latency_budget_ms": 0,
        # partial-flush padding placement: false (default) pads on host
        # to frames-out — universal, but the pad rows cross the H2D link
        # too. true emits only the k real frames plus
        # meta["pad_rows"]; a downstream prefetch-device queue applies
        # the zero-pad ON DEVICE (tensors/buffer.py pad_rows_device), so
        # the wire carries k frames while the jitted filter still sees
        # its one compiled frames-out shape. Requires such a queue
        # downstream — without one the filter sees [k] and recompiles
        # per distinct k.
        "pad_device": False,
    }

    def __init__(self, name=None, **props):
        super().__init__(name, **props)
        self.add_sink_pad("sink")
        self.add_src_pad("src")
        #: one window per tensor position in the frame — every tensor of a
        #: multi-tensor stream is aggregated, none silently dropped
        self._windows: List[List[np.ndarray]] = []
        self._pts: Optional[int] = None
        #: capture timestamps of the unit frames in flight, parallel to the
        #: windows — emitted as meta["create_ts"] so end-to-end latency
        #: under micro-batching includes each frame's batch-window wait
        self._create_ts: List[float] = []
        #: admission stamps (meta["admitted_t"] from a stamp-admission
        #: queue upstream), in lockstep with the windows like _create_ts
        #: — emitted as meta["admitted_ts"] so the sink's served-traffic
        #: latency population survives micro-batching
        self._admit_ts: List[float] = []
        #: trace seqs of the unit frames in flight (timeline active
        #: only), same lockstep discipline as _create_ts — a combined
        #: window adopts its earliest constituent's frame identity
        self._tl_seqs: List[Optional[int]] = []
        #: budget clock per queued unit frame: its create stamp when one
        #: flowed (end-to-end budget), else its aggregator arrival time
        self._held_since: List[float] = []
        #: serializes chain() with the budget flusher thread — both push
        #: downstream, and a flush must not interleave with window append
        self._lock = threading.RLock()
        self._flusher: Optional[threading.Thread] = None
        self._stop_evt = threading.Event()

    def start(self):
        super().start()
        budget = float(self.get_property("latency_budget_ms"))
        if budget > 0:
            self._stop_evt.clear()
            self._flusher = threading.Thread(
                target=self._flush_loop, args=(budget / 1e3,),
                daemon=True, name=f"{self.name}-budget")
            self._flusher.start()

    def stop(self):
        self._stop_evt.set()
        if self._flusher is not None:
            self._flusher.join(timeout=5)
            self._flusher = None
        super().stop()

    def note_mesh_quantum(self, quantum: int) -> None:
        """Mesh-wide batch forming (parallel/serve.py): round frames-out
        up to a multiple of the pipeline's dp shard count so every full
        window this former emits splits evenly across the mesh. A
        non-multiple window is still legal — the sharded region falls
        back to a replicated invoke for it — but it serializes the batch
        onto one shard, so the former should not produce one by
        construction. Called by Pipeline.start() once the sharded plan
        is known; pass-through configs (frames-out == 1) are left alone
        because the user asked for per-frame service, not batching."""
        q = max(1, int(quantum))
        fout = int(self.get_property("frames_out"))
        if q <= 1 or fout <= 1 or fout % q == 0:
            return
        rounded = ((fout + q - 1) // q) * q
        log.info("%s: frames-out %d -> %d (mesh shard quantum %d)",
                 self.name, fout, rounded, q)
        self.set_property("frames_out", rounded)

    def transform_caps(self, pad, caps):
        return None  # announced from the first output (shape changes)

    def _axis(self, arr) -> int:
        return arr.ndim - 1 - int(self.get_property("frames_dim"))

    def chain(self, pad, buf):
        with self._lock:
            return self._chain_locked(pad, buf)

    def chain_list(self, pad, bufs):
        """Batch-drain fast path: the whole queue backlog windows under
        one lock acquisition (the flusher thread contends once per
        backlog instead of once per frame)."""
        ret = None
        with self._lock:
            for b in bufs:
                ret = self._chain_locked(pad, b)
        return ret

    def _chain_locked(self, pad, buf):
        fin = int(self.get_property("frames_in"))
        fout = int(self.get_property("frames_out"))
        flush = int(self.get_property("frames_flush")) or fout
        if not buf.tensors:
            return None  # empty frame: nothing to window (and `all([])`
            # below would spin forever)
        if not self._windows:
            self._windows = [[] for _ in buf.tensors]
        elif len(buf.tensors) != len(self._windows):
            raise ValueError(
                f"tensor_aggregator: frame has {len(buf.tensors)} tensors, "
                f"stream started with {len(self._windows)}"
            )
        if self._pts is None:
            self._pts = buf.pts
        n = max(fin, 1)
        # validate every tensor BEFORE mutating windows or stamps: a
        # mid-loop failure would leave them desynchronized for any caller
        # that catches the error and keeps streaming
        for arr in buf.tensors:
            axis = self._axis(arr)
            if arr.shape[axis] % n:
                raise ValueError(
                    f"tensor_aggregator: dim "
                    f"{self.get_property('frames_dim')} size "
                    f"{arr.shape[axis]} not divisible by frames-in {n}"
                )
        stamps = buf.create_stamps()
        if stamps:
            # exactly one stamp per unit frame keeps the stamp list in
            # lockstep with the windows; when the carried stamp count
            # doesn't match the frames_in split (e.g. a muxed buffer with
            # one stamp per input stream), use the EARLIEST stamp for all
            # of them — conservative (reports the longest latency)
            if len(stamps) != n:
                stamps = [min(stamps)] * n
        if stamps or self._create_ts:
            # mixed stamped/unstamped upstreams (frames pushed straight
            # into srcpad.push interleaved with SourceElement frames)
            # must not shift stamp→window attribution: pad any historical
            # deficit and this buffer's missing stamps with None
            # placeholders so indices stay aligned (filtered at emit)
            deficit = max(0, len(self._windows[0]) - len(self._create_ts))
            self._create_ts.extend([None] * deficit)
            self._create_ts.extend(stamps if stamps else [None] * n)
        if _timeline.ACTIVE is not None or self._tl_seqs:
            deficit = max(0, len(self._windows[0]) - len(self._tl_seqs))
            self._tl_seqs.extend([None] * deficit)
            self._tl_seqs.extend(
                [buf.meta.get(_timeline.TRACE_SEQ_META)] * n)
        adm = buf.meta.get("admitted_t")
        if adm is not None or self._admit_ts:
            # same alignment discipline as _create_ts: the buffer's one
            # admission stamp covers each of its unit frames
            deficit = max(0, len(self._windows[0]) - len(self._admit_ts))
            self._admit_ts.extend([None] * deficit)
            self._admit_ts.extend([adm] * n)
        budget = float(self.get_property("latency_budget_ms"))
        if budget > 0:
            now = time.monotonic()
            self._held_since.extend(
                (stamps[i] if stamps and stamps[i] is not None else now)
                for i in range(n))
        for ti, arr in enumerate(buf.tensors):
            axis = self._axis(arr)
            # split the incoming tensor into its `frames_in` unit frames
            per = arr.shape[axis] // n
            for k in range(n):
                sl = [slice(None)] * arr.ndim
                sl[axis] = slice(k * per, (k + 1) * per)
                self._windows[ti].append(arr[tuple(sl)])
        ret = None
        while all(len(w) >= fout for w in self._windows):
            outs = self._concat_windows(
                [w[:fout] for w in self._windows])
            self._announce_caps(outs)
            meta = {}
            if self._create_ts:
                out_ts = [s for s in self._create_ts[:fout]
                          if s is not None]
                if out_ts:
                    meta["create_ts"] = out_ts
            if self._admit_ts:
                out_adm = [s for s in self._admit_ts[:fout]
                           if s is not None]
                if out_adm:
                    meta["admitted_ts"] = out_adm
            seq = next((s for s in self._tl_seqs[:fout]
                        if s is not None), None)
            if seq is not None:
                meta[_timeline.TRACE_SEQ_META] = seq
            ret = self.srcpad.push(
                TensorBuffer(outs, pts=self._pts, meta=meta)
            )
            self._windows = [w[flush:] for w in self._windows]
            self._create_ts = self._create_ts[flush:]
            self._admit_ts = self._admit_ts[flush:]
            self._tl_seqs = self._tl_seqs[flush:]
            self._held_since = self._held_since[flush:]
            self._pts = buf.pts
        if budget > 0 and self._held_since and \
                time.monotonic() - self._held_since[0] >= budget / 1e3 \
                and self._downstream_ready():
            ret = self._emit_partial() or ret
        return ret

    def _downstream_ready(self) -> bool:
        """Backpressure gate for budget flushes: a partial flush is a
        latency optimization, and it only helps while the downstream can
        absorb the extra dispatch. When the link/device is saturated
        (the downstream queue is full), flushing MORE, SMALLER windows
        compounds the backlog. Holding instead lets the window fill
        toward a full
        batch, i.e. budget mode degrades gracefully to plain batching
        under overload. Full windows are exempt: they flush through the
        normal (blocking) path regardless."""
        peer = self.srcpad.peer
        ready = getattr(getattr(peer, "element", None), "accepts_now",
                        None)
        return True if ready is None else bool(ready())

    def _flush_loop(self, budget_s: float):
        """Budget watchdog: chain() only runs on arrivals, so a stalled
        upstream would otherwise hold queued frames past the budget
        forever. Ticks at budget/4 → a frame overstays by at most ~25%."""
        tick = max(budget_s / 4, 0.005)
        while not self._stop_evt.wait(tick):
            with self._lock:
                if self._held_since and \
                        time.monotonic() - self._held_since[0] >= budget_s \
                        and self._downstream_ready():
                    self._emit_partial()

    def _concat_windows(self, chunks):
        """Emit-side payload assembly shared by the full-window and
        budget-flush paths: one concatenated tensor per window
        (concat=true) or the unit frames as separate tensors."""
        outs = []
        for chunk in chunks:
            if self.get_property("concat"):
                axis = self._axis(chunk[0])
                if is_device_array(chunk[0]):
                    import jax.numpy as jnp

                    outs.append(jnp.concatenate(chunk, axis=axis))
                elif all(c.dtype == chunk[0].dtype for c in chunk):
                    # host windows assemble into a recycled staging
                    # buffer (tensors/pool.py): at flagship rates this
                    # concat is the ingest path's one per-window
                    # allocation, and the pooled buffer recycles once
                    # the H2D that consumes it fences downstream
                    from nnstreamer_tpu.tensors.pool import get_pool

                    shape = list(chunk[0].shape)
                    shape[axis] = sum(c.shape[axis] for c in chunk)
                    dst = get_pool().acquire(shape, chunk[0].dtype)
                    np.concatenate(chunk, axis=axis, out=dst)
                    outs.append(dst)
                else:
                    # mixed dtypes promote — let numpy own the result
                    outs.append(np.concatenate(chunk, axis=axis))
            else:
                # concat=false: collected frames stay separate tensors
                # (reference tensor_aggregator concat property)
                outs.extend(chunk)
        return outs

    def _announce_caps(self, outs):
        if self.srcpad.caps is None:
            from nnstreamer_tpu.tensors.types import TensorsConfig

            self.srcpad.set_caps(TensorsConfig.from_arrays(outs).to_caps())

    def _emit_partial(self):
        """Flush the queued k < frames-out frames. With concat=true on a
        leading (axis-0) frame axis the window is padded to frames-out
        (one compiled downstream shape) and ``meta["valid_frames"]=k``
        lets the sink trim the padding; ``pad-device`` defers that pad
        to a downstream prefetch-device queue so only the k real frames
        cross the H2D link. Non-leading concat axes and concat=false
        emit the k real frames UNPADDED (self-describing shapes — the
        sink's axis-0 trim cannot apply there). Caller holds
        ``self._lock``."""
        fout = int(self.get_property("frames_out"))
        k = len(self._windows[0]) if self._windows else 0
        if not k:
            return None
        pad_ok = (self.get_property("concat") and k < fout and
                  self._axis(self._windows[0][0]) == 0)
        # the device-pad path needs announced caps (set below from a
        # host-padded first window)
        on_device_pad = (pad_ok and bool(self.get_property("pad_device"))
                         and self.srcpad.caps is not None)
        pad_n = (fout - k) if (pad_ok and not on_device_pad) else 0
        outs = self._concat_windows(
            [list(w) + [w[-1]] * pad_n for w in self._windows])
        if not on_device_pad:
            self._announce_caps(outs)
        meta = {}
        if pad_ok:
            meta["valid_frames"] = k
            if on_device_pad:
                meta["pad_rows"] = fout - k
        out_ts = [s for s in self._create_ts[:k] if s is not None]
        if out_ts:
            meta["create_ts"] = out_ts
        out_adm = [s for s in self._admit_ts[:k] if s is not None]
        if out_adm:
            meta["admitted_ts"] = out_adm
        seq = next((s for s in self._tl_seqs[:k] if s is not None), None)
        if seq is not None:
            meta[_timeline.TRACE_SEQ_META] = seq
        ret = self.srcpad.push(TensorBuffer(outs, pts=self._pts, meta=meta))
        self._windows = [[] for _ in self._windows]
        self._create_ts = []
        self._admit_ts = []
        self._tl_seqs = []
        self._held_since = []
        self._pts = None
        return ret

    def handle_eos(self):
        with self._lock:
            if float(self.get_property("latency_budget_ms")) > 0:
                # budget mode promises every frame a bounded exit: the
                # partial tail flushes instead of being dropped
                self._emit_partial()
            self._windows.clear()
            self._create_ts.clear()
            self._admit_ts.clear()
            self._tl_seqs.clear()
            self._held_since.clear()
            self._pts = None
