"""Frame-ledger timeline: per-frame lifecycle spans across the async
substrate (lanes, queues, scheduler, dispatch window, transfers).

A slice per element invoke was the whole story when the pipeline WAS its
chain calls. Everything built since is asynchronous: DispatchWindow keeps
K device batches in flight, lane workers process frames out of order
behind a reorder buffer, the SLO scheduler holds frames in an EDF heap
and sheds them, DeviceBuffers defer their D2H to the sink. None of that
shows up in a chain-wrapped trace. This module records where a FRAME's
time actually goes.

Recording model
---------------
A :class:`Timeline` is installed process-wide (``ACTIVE``). The source
thread stamps a monotone sequence id (``meta["trace_seq"]``) on every
frame — the same single-writer monotone-id discipline the lane executor
already uses for reorder reassembly — and instrumentation points across
the stack append typed spans keyed by that id. Each recording thread
appends into its own bounded ring (``deque(maxlen=capacity)``): no
lock, no allocation beyond the tuple, GIL-atomic append. Export drains
every ring, so a span is attributed to the thread that recorded it
(lane workers, queue drains, the source loop each get their own track).

With no timeline installed (``ACTIVE is None`` — the default) every
instrumentation site is a single module-attribute read and an ``is
None`` test: the off path stays byte-identical and effectively free,
matching the ``NNSTPU_RESIDENT`` / ``NNSTPU_LANES`` kill-switch
discipline.

Stage semantics (the frame ledger)
----------------------------------
The canonical span kinds in :data:`STAGES` tile a frame's critical
path, so their per-frame sums reconcile with the sink's end-to-end
latency:

- ``ingest``      source ``create()`` → first queue entry (host
                  preprocessing, minus any reorder-buffer wait)
- ``lane_reorder``time parked in the lane reorder buffer
- ``queue_wait``  FIFO queue residency (entry → drain pop)
- ``sched_hold``  EDF-heap residency in a scheduler-mode queue
- ``fence_wait``  dispatch-window fence block for the frame's own entry
- ``shard``       mesh placement of the frame's tensors onto the serving
                  mesh (sharded fused regions only; zero/absent on
                  single-device pipelines and matched hand-offs)
- ``device``      filter/fused-region invoke dispatch
- ``d2h``         the sanctioned ``to_host()`` materialization block
- ``decode``      tensor→media decode (host part)
- ``sink``        sink-side completion work after materialization

Non-tiling kinds (``h2d``, ``lane_exec``, ``lane_stall``) and instant
events (``sched_reject``, ``sched_shed``, ``sched_revoked``,
``submit``) appear in the exported trace but are excluded from the
reconciliation sum — they overlap the stages above in wall time.

Export
------
:meth:`Timeline.to_chrome` emits Chrome trace-event JSON that Perfetto
loads directly: one process, one named thread track per recording
thread / lane / queue, ``ph:"X"`` slices with ``args`` carrying the
frame seq, ``s``/``t``/``f`` flow events linking one frame across
tracks, and ``b``/``e`` async spans for dispatch-window inflight slots.
:meth:`stage_breakdown` aggregates the same records into per-stage
means that must sum to ~e2e; :meth:`variance_report` attributes
warm-run spread to its dominant stage.

One clock with the device trace
-------------------------------
Spans are recorded after the fact on ``time.monotonic()``; the JAX
profiler stamps device events in Unix-epoch nanoseconds. A timeline
notes one ``(time.monotonic(), time.time_ns())`` pair when it is made,
:meth:`Timeline.to_trace_ns` converts with it, and
:func:`device_trace` runs the profiler (device tracer only) over a
window and writes the ledger's spans on the trace's clock beside the
device's program executions, so a gap on the device line lies over the
host span that caused it; :func:`idle_by_span` is that reading as
numbers, the seconds of gap under each span kind.
"""

from __future__ import annotations

import glob
import itertools
import json
import os
import statistics
import threading
import time
import weakref
from collections import deque
from contextlib import contextmanager
from typing import Any, Dict, List, Optional, Tuple

#: meta key carrying the frame's trace context: a monotone sequence id
#: stamped once by the source thread (single writer, like the lane
#: executor's ``lane_seq``)
TRACE_SEQ_META = "trace_seq"

#: span kinds that tile a frame's critical path — the stage_breakdown /
#: reconciliation set, in pipeline order
LOCAL_STAGES: Tuple[str, ...] = ("ingest", "lane_reorder", "queue_wait",
                                 "sched_hold", "fence_wait", "shard",
                                 "device", "d2h", "decode", "sink")

#: distributed-hop stages spliced into the CLIENT ledger by
#: elements/query.py when cross-hop tracing is armed (obs/distributed):
#: outbound wire time, the remote pipeline's queue/device residency, the
#: remote remainder (decode/sink/unattributed), and inbound wire time.
#: All five are anchored inside the client's observed RTT window — raw
#: remote clocks are never compared against local ones — and stay
#: zero-valued (absent) on single-process pipelines, so every consumer
#: keyed off STAGES (flight quantiles, gauges, MAD attribution,
#: breakdowns) names remote stages without further wiring.
DIST_STAGES: Tuple[str, ...] = ("hop_send", "remote_queue",
                                "remote_device", "remote_other",
                                "hop_recv")

STAGES: Tuple[str, ...] = LOCAL_STAGES + DIST_STAGES

_ENV = "NNSTPU_TRACE"

#: the process-wide active timeline; ``None`` means tracing is OFF and
#: every instrumentation site reduces to one attribute read + is-None
#: test. Hot paths read this directly (``_timeline.ACTIVE``).
ACTIVE: Optional["Timeline"] = None


def trace_enabled() -> bool:
    """True when ``NNSTPU_TRACE`` asks for tracing (any non-empty value
    except the usual falsy spellings; a value that is not a boolean
    spelling is taken as the export path)."""
    v = os.environ.get(_ENV, "").strip()
    return bool(v) and v.lower() not in ("0", "false", "no", "off")


def env_export_path() -> Optional[str]:
    """The export path carried in ``NNSTPU_TRACE``, if it names one."""
    v = os.environ.get(_ENV, "").strip()
    if not v or v.lower() in ("0", "false", "no", "off", "1", "true",
                              "yes", "on"):
        return None
    return v


def active() -> Optional["Timeline"]:
    return ACTIVE


def activate(capacity: int = 1 << 16) -> "Timeline":
    """Install a fresh process-wide timeline and return it."""
    global ACTIVE
    tl = Timeline(capacity)
    ACTIVE = tl
    return tl


def deactivate() -> None:
    global ACTIVE
    ACTIVE = None


@contextmanager
def tracing(capacity: int = 1 << 16):
    """Scoped activation: ``with tracing() as tl: pipe.run(...)``."""
    tl = activate(capacity)
    try:
        yield tl
    finally:
        if ACTIVE is tl:
            deactivate()


def maybe_activate_env() -> Optional["Timeline"]:
    """``Pipeline.start()`` hook: honor ``NNSTPU_TRACE`` without code
    changes. Idempotent; an explicitly installed timeline wins."""
    if ACTIVE is not None:
        return ACTIVE
    if not trace_enabled():
        return None
    tl = activate()
    tl.export_path = env_export_path()
    tl._env_owned = True
    return tl


def maybe_export_env() -> None:
    """``Pipeline.stop()`` hook: export + retire an env-owned timeline
    (``NNSTPU_TRACE=/path/to/trace.json``)."""
    tl = ACTIVE
    if tl is None or not tl._env_owned:
        return
    if tl.export_path:
        try:
            tl.export_chrome(tl.export_path)
        except OSError:
            pass  # an unwritable path must not take down pipeline stop
    deactivate()


#: the device plane's line of program executions in a profiler trace
_MODULES_LINE = "XLA Modules"


def program_events(xplane_path: str) -> List[Tuple[str, str, int, int]]:
    """``(device, program, start_ns, end_ns)`` of every program execution
    in a profiler trace, by start time, in Unix-epoch nanoseconds;
    ``jit_step(123)`` reads ``jit_step``. The device plane counts from
    the profile's start (looked at by hand, libtpu 0.0.34: the first
    event of a trace at 41,629,803 ns), and the plane ``Task
    Environment`` says when that was (``profile_start_time``)."""
    from jax.profiler import ProfileData

    out, start = [], 0
    for plane in ProfileData.from_file(xplane_path).planes:
        if plane.name == "Task Environment":
            start = int(dict(plane.stats).get("profile_start_time", 0))
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name != _MODULES_LINE:
                continue
            for ev in line.events:
                out.append((plane.name, ev.name.split("(")[0].strip(),
                            round(ev.start_ns),
                            round(ev.start_ns + ev.duration_ns)))
    # whole numbers: a float holds 1.8e18 ns to the nearest 256
    return sorted(((d, n, start + a, start + b) for d, n, a, b in out),
                  key=lambda e: e[2])


def clock_differences(host: List[Tuple[int, int]],
                      device: List[Tuple[int, int]]
                      ) -> List[Tuple[int, int]]:
    """``(host start - device start, host end - device end)``, the k-th
    host span against the k-th device event, both as ``(start_ns,
    end_ns)``. The window's edges may cut the two lists differently, so
    the pairing is tried shifted by up to three places and the one whose
    ends lie closest is taken: right as long as the clocks differ by
    less than half the time between two executions."""
    best: List[Tuple[int, int]] = []

    def ends_apart(pairs):
        return abs(statistics.median(p[1] for p in pairs))

    for shift in range(-3, 4):
        pairs = [(h[0] - device[i + shift][0], h[1] - device[i + shift][1])
                 for i, h in enumerate(host) if 0 <= i + shift < len(device)]
        if pairs and (not best or ends_apart(pairs) < ends_apart(best)):
            best = pairs
    return best


def idle_by_span(programs: List[Tuple[str, str, int, int]],
                 spans: List[Tuple[str, int, int]],
                 window: Tuple[int, int]) -> Dict[str, float]:
    """The device's idle gaps put down to the host spans over them.

    ``programs`` are ``(device, program, start_ns, end_ns)`` as
    :func:`program_events` gives them, ``spans`` are ``(kind, start_ns,
    end_ns)`` on the same clock, ``window`` is ``(start_ns, end_ns)``. A
    gap is the time between two program executions of one device inside
    the window (the union of its executions, as the benchmark's
    ``trace_reduce.reduce_events`` takes them; several devices add up).
    Returns seconds: for every kind among ``spans`` the part of the gaps
    that lies under a span of that kind, ``gap_s`` (all of it) and
    ``unattributed_s`` (under no span). Spans that tile a thread, as the
    engine loop's ``lm_*`` do, leave unattributed only what the clocks'
    mismatch and the window's edges cut off."""
    w0, w1 = window
    by_device: Dict[str, List[Tuple[int, int]]] = {}
    for dev, _, a, b in programs:
        a, b = max(a, w0), min(b, w1)
        if a < b:
            by_device.setdefault(dev, []).append((a, b))
    spans = sorted((s for s in spans if s[1] < s[2]), key=lambda s: s[1])
    out = {kind: 0 for kind, _, _ in spans}
    total = unattributed = 0
    for busy in by_device.values():
        busy.sort()
        end = busy[0][1]
        gaps = []
        for a, b in busy[1:]:
            if a > end:
                gaps.append((end, a))
            end = max(end, b)
        lo = 0  # spans before it ended before the gap at hand began
        for g0, g1 in gaps:
            while lo < len(spans) and spans[lo][2] <= g0:
                lo += 1
            covered, reach = 0, g0
            for i in range(lo, len(spans)):
                kind, a, b = spans[i]
                if a >= g1:
                    break
                a, b = max(a, g0), min(b, g1)
                if a < b:
                    out[kind] += b - a
                    covered += max(b - max(a, reach), 0)
                    reach = max(reach, b)
            total += g1 - g0
            unattributed += g1 - g0 - covered
    return {**{k: v / 1e9 for k, v in out.items()}, "gap_s": total / 1e9,
            "unattributed_s": unattributed / 1e9}


class DeviceTrace:
    """What :func:`device_trace` yields; filled in when the block ends."""

    def __init__(self, logdir: str, ledger: Optional["Timeline"],
                 track: Optional[str] = None):
        self.logdir = logdir
        self.ledger = ledger
        self.track = track
        self.window: Tuple[float, float] = (0.0, 0.0)  # time.monotonic()
        self.xplane: Optional[str] = None       # the profiler's file
        self.ledger_path: Optional[str] = None  # the ledger, trace clock
        #: ``(device, program, start_ns, end_ns)`` from the trace
        self.programs: List[Tuple[str, str, int, int]] = []
        #: added to ``ledger.to_trace_ns()``: what matching found
        self.offset_ns = 0
        #: the matching, before the correction, when ``align`` was given:
        #: ``n`` pairs, ``lead_ns`` (host start minus device start, the
        #: largest) and ``lag_min_ns``/``lag_median_ns``/``lag_max_ns``
        #: (host end minus device end)
        self.clock_check: Optional[Dict[str, float]] = None
        #: :func:`idle_by_span` over the window: the seconds of the
        #: device's idle gaps under each span kind of the ledger (of
        #: ``track`` alone if one was given), after ``offset_ns``
        self.idle_by_span: Optional[Dict[str, float]] = None

    def to_trace_ns(self, t: float) -> int:
        return self.ledger.to_trace_ns(t) + self.offset_ns

    def _join(self) -> None:
        to_ns = self.to_trace_ns
        spans = [(r[1], to_ns(r[3]), to_ns(r[4]))
                 for r in self.ledger._snapshot() if r[4] is not None
                 and (self.track is None or (r[5] or r[0]) == self.track)]
        self.idle_by_span = idle_by_span(
            self.programs, spans, (to_ns(self.window[0]),
                                   to_ns(self.window[1])))

    def _align(self, span_kind: str, program: str) -> None:
        t_a, t_b = self.window
        to_ns = self.ledger.to_trace_ns
        host = [(to_ns(r[3]), to_ns(r[4])) for r in self.ledger._snapshot()
                if r[1] == span_kind and r[4] is not None
                and t_a <= r[3] and r[4] <= t_b]
        device = [(e[2], e[3]) for e in self.programs if e[1] == program]
        pairs = clock_differences(host, device)
        if not pairs:
            return
        lead = max(p[0] for p in pairs)
        lags = [p[1] for p in pairs]
        self.clock_check = {"n": len(pairs), "lead_ns": lead,
                            "lag_min_ns": min(lags),
                            "lag_median_ns": statistics.median(lags),
                            "lag_max_ns": max(lags)}
        # the span holds the device event: any offset from -lag to -lead
        # is possible, the middle is wrong by the least
        self.offset_ns = -(min(lags) + lead) // 2

    def _write(self) -> None:
        doc = self.ledger.to_chrome()
        # the export counts microseconds from the ledger's epoch
        shift_us = self.to_trace_ns(self.ledger.epoch) / 1e3
        for ev in doc["traceEvents"]:
            if "ts" in ev:
                ev["ts"] += shift_us
        pids = {dev: 1000 + i for i, dev in enumerate(
            sorted({e[0] for e in self.programs}))}
        for dev, pid in pids.items():
            doc["traceEvents"].append(
                {"name": "process_name", "ph": "M", "pid": pid,
                 "args": {"name": dev}})
            doc["traceEvents"].append(
                {"name": "thread_name", "ph": "M", "pid": pid, "tid": 1,
                 "args": {"name": _MODULES_LINE}})
        for dev, name, start, end in self.programs:
            doc["traceEvents"].append(
                {"name": name, "cat": "device", "ph": "X",
                 "ts": start / 1e3, "dur": (end - start) / 1e3,
                 "pid": pids[dev], "tid": 1})
        doc["metadata"]["clock"]["offset_ns"] = self.offset_ns
        doc["metadata"]["idle_by_span"] = self.idle_by_span
        self.ledger_path = os.path.join(self.logdir, "ledger.trace.json")
        with open(self.ledger_path, "w") as f:
            json.dump(doc, f)


@contextmanager
def device_trace(logdir: str, ledger: Optional["Timeline"] = None,
                 align: Optional[Tuple[str, str]] = None,
                 track: Optional[str] = None):
    """Run ``jax.profiler`` over the block with the DEVICE tracer only
    (the host and Python tracers slowed a traced pipeline five-fold:
    PERF.md, PR 24) and, when the block ends, write the ledger's spans
    on the trace's clock to ``<logdir>/ledger.trace.json`` together with
    the device's program executions: one file for Perfetto in which a
    gap on the device line lies over the host span that caused it. The
    profiler's own ``.xplane.pb`` (every operation) stays where it wrote
    it. ``ledger`` defaults to the installed timeline.

    ``align=(span_kind, program)`` checks the common clock, and sets it
    right, by matching the k-th ``span_kind`` span of the window against
    the k-th execution of ``program``. The span has to be one that
    issues the program and waits for its result: it then holds the
    device event, with the launch before it and the fetch after. How far
    the span leads and lags goes to ``clock_check``; the offset that
    splits the slack evenly goes to ``offset_ns`` and into the written
    file. On a v5e the device's clock read 0.3 to 3.6 ms behind the
    host's (PERF.md, PR 25): without ``align`` a span shorter than that
    can lie beside the gap it caused.

    After the correction every idle gap of the device inside the window
    is put down to the spans over it (:func:`idle_by_span`): the seconds
    under each span kind go to ``idle_by_span`` and into the file's
    ``metadata``. ``track`` keeps that to the spans of one track (an
    engine's ``obs_name``) where the ledger holds other threads' too.

    Yields a :class:`DeviceTrace`."""
    import jax

    ledger = ledger if ledger is not None else ACTIVE
    out = DeviceTrace(logdir, ledger, track)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(logdir, profiler_options=options)
    t_a = time.monotonic()
    try:
        yield out
    finally:
        out.window = (t_a, time.monotonic())
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb"))
    if files:
        out.xplane = max(files, key=os.path.getmtime)
        out.programs = program_events(out.xplane)
    if ledger is not None:
        if align is not None:
            out._align(*align)
        out._join()
        out._write()


class _RingAnchor:
    """Weakref-able token parked in a recording thread's thread-local
    dict; its finalizer retires the thread's ring (see ``_ring``)."""

    __slots__ = ("__weakref__",)


def _retire_ring(tl_ref: "weakref.ref", entry: Tuple[str, deque]) -> None:
    tl = tl_ref()
    if tl is not None:
        tl._retire(entry)


class Timeline:
    """Low-overhead frame-ledger recorder (see module docstring)."""

    def __init__(self, capacity: int = 1 << 16):
        self.capacity = int(capacity)
        self.epoch = time.monotonic()
        #: one reading of both clocks: spans are recorded on the first,
        #: the device trace is stamped on the second (``to_trace_ns``)
        self.clock: Tuple[float, int] = (time.monotonic(), time.time_ns())
        self.export_path: Optional[str] = None
        self._env_owned = False
        self._seq = itertools.count()  # next() is GIL-atomic
        self._local = threading.local()
        #: [(thread_name, ring)] — registry of every LIVE thread's ring;
        #: appended once per recording thread under the lock, drained
        #: at export, removed when the thread dies (see ``_ring``)
        self._rings: List[Tuple[str, deque]] = []
        self._rings_lock = threading.Lock()
        #: records salvaged from dead threads' rings — supervised lane
        #: restarts spin up fresh worker threads per crash cycle, so
        #: without retirement ``_rings`` grows one entry per restart
        #: forever; bounded like any single ring
        self._retired: deque = deque(maxlen=self.capacity)
        #: dispatch-window inflight slots: ("b"/"e", name, id, t, track)
        self._async: deque = deque(maxlen=4 * self.capacity)

    # -- recording (hot path) ------------------------------------------------
    def next_seq(self) -> int:
        return next(self._seq)

    def _ring(self) -> deque:
        r = getattr(self._local, "ring", None)
        if r is None:
            r = deque(maxlen=self.capacity)
            entry = (threading.current_thread().name, r)
            with self._rings_lock:
                self._rings.append(entry)
            # Unregister at thread death: the anchor lives only in this
            # thread's thread-local dict, so CPython drops it when the
            # thread exits and the finalizer moves the ring's records
            # into the bounded ``_retired`` store. Pipeline.stop() joins
            # workers before export, so post-join exports still see
            # every span; what this prevents is ``_rings`` growing one
            # dead entry per supervised lane restart.
            anchor = _RingAnchor()
            weakref.finalize(anchor, _retire_ring, weakref.ref(self),
                             entry)
            self._local.ring = r
            self._local.anchor = anchor
        return r

    def _retire(self, entry: Tuple[str, deque]) -> None:
        name, ring = entry
        with self._rings_lock:
            try:
                self._rings.remove(entry)
            except ValueError:
                return  # clear()/re-entry already handled it
            for rec in ring:
                self._retired.append((name,) + rec)

    def span(self, kind: str, seq: Optional[int], t0: float, t1: float,
             track: Optional[str] = None, **args) -> None:
        """Record a duration span [t0, t1) attributed to frame ``seq``."""
        self._ring().append((kind, seq, t0, t1, track, args or None))

    def extend_last(self, kind: str, t1: float) -> bool:
        """Lengthen this thread's newest record to ``t1`` if it is a
        ``kind`` span, so that consecutive waits are one record and an
        idle loop does not flush its ring. False if it is not."""
        ring = self._ring()
        if not ring or ring[-1][0] != kind or ring[-1][3] is None:
            return False
        _, seq, t0, _, track, args = ring[-1]
        ring[-1] = (kind, seq, t0, t1, track, args)
        return True

    def mark(self, kind: str, seq: Optional[int],
             t: Optional[float] = None, track: Optional[str] = None,
             **args) -> None:
        """Record an instant event (shed/reject decisions, submits)."""
        if t is None:
            t = time.monotonic()
        self._ring().append((kind, seq, t, None, track, args or None))

    def async_begin(self, name: str, aid: int,
                    t: Optional[float] = None,
                    track: str = "dispatch") -> None:
        self._async.append(
            ("b", name, aid, time.monotonic() if t is None else t, track))

    def async_end(self, name: str, aid: int,
                  t: Optional[float] = None,
                  track: str = "dispatch") -> None:
        self._async.append(
            ("e", name, aid, time.monotonic() if t is None else t, track))

    def clear(self) -> None:
        """Drop recorded events (rings stay registered; epoch advances
        so a re-used timeline exports a fresh window)."""
        with self._rings_lock:
            rings = list(self._rings)
            self._retired.clear()
        for _, r in rings:
            r.clear()
        self._async.clear()
        self.epoch = time.monotonic()

    # -- aggregation ---------------------------------------------------------
    def _snapshot(self) -> List[tuple]:
        """All records as (thread, kind, seq, t0, t1, track, args),
        time-ordered."""
        with self._rings_lock:
            rings = list(self._rings)
            retired = list(self._retired)
        out: List[tuple] = list(retired)
        for tname, ring in rings:
            for rec in list(ring):
                out.append((tname,) + rec)
        out.sort(key=lambda r: r[3])
        return out

    def frame_ledger(self, skip_frames: int = 0
                     ) -> Dict[int, Dict[str, float]]:
        """Per-frame stage durations (seconds) keyed by trace seq; a
        frame that reached the sink also carries its measured ``e2e``.
        ``skip_frames`` drops the first N frames (warm-up exclusion)."""
        frames: Dict[int, Dict[str, float]] = {}
        for _, kind, seq, t0, t1, _, args in self._snapshot():
            if seq is None or t1 is None:
                continue
            d = frames.setdefault(seq, {})
            d[kind] = d.get(kind, 0.0) + (t1 - t0)
            if args and "e2e_s" in args:
                d["e2e"] = float(args["e2e_s"])
        for s in sorted(frames)[:skip_frames]:
            del frames[s]
        return frames

    def frame_stages(self, seq: int) -> Dict[str, float]:
        """Stage durations (seconds) for ONE frame — the scan-based
        span-vector source a query server uses for remote egress when
        no flight recorder (with its O(1) per-frame accumulator) is
        installed."""
        out: Dict[str, float] = {}
        for _, kind, s, t0, t1, _, _ in self._snapshot():
            if s == seq and t1 is not None:
                out[kind] = out.get(kind, 0.0) + (t1 - t0)
        return out

    def stage_breakdown(self, skip_frames: int = 0) -> Dict[str, Any]:
        """Mean per-frame seconds spent in each canonical stage, over
        frames that completed (have a sink e2e record). ``covered_ms``
        is the sum of the stage means; ``reconciliation`` is
        covered/e2e — ~1.0 means the ledger accounts for the frame's
        whole life, a gap shows as ``unattributed_ms``."""
        frames = self.frame_ledger(skip_frames)
        done = [d for d in frames.values() if "e2e" in d]
        n = len(done)
        if n == 0:
            return {"frames": 0, "stages_ms": {}, "e2e_mean_ms": 0.0,
                    "covered_ms": 0.0, "unattributed_ms": 0.0,
                    "reconciliation": 0.0}
        stages = {k: sum(d.get(k, 0.0) for d in done) / n * 1e3
                  for k in STAGES}
        e2e = sum(d["e2e"] for d in done) / n * 1e3
        covered = sum(stages.values())
        return {
            "frames": n,
            "stages_ms": {k: round(v, 4) for k, v in stages.items()},
            "e2e_mean_ms": round(e2e, 4),
            "covered_ms": round(covered, 4),
            "unattributed_ms": round(max(e2e - covered, 0.0), 4),
            "reconciliation": round(covered / e2e, 4) if e2e > 0 else 0.0,
        }

    def variance_report(self, skip_frames: int = 0) -> Dict[str, Any]:
        """Attribute e2e spread to its dominant stage: per-stage MAD of
        the per-frame durations (robust — one cold outlier cannot own
        the report), ranked; ``dominant_share`` is the winner's MAD as
        a fraction of the e2e MAD."""
        frames = self.frame_ledger(skip_frames)
        done = [d for d in frames.values() if "e2e" in d]
        if len(done) < 2:
            return {"frames": len(done), "e2e_mad_ms": 0.0,
                    "stage_mad_ms": {}, "dominant_stage": None,
                    "dominant_share": 0.0}

        def _mad(vals: List[float]) -> float:
            vals = sorted(vals)
            med = vals[len(vals) // 2]
            dev = sorted(abs(v - med) for v in vals)
            return dev[len(dev) // 2]

        stage_mad = {k: _mad([d.get(k, 0.0) for d in done]) * 1e3
                     for k in STAGES}
        e2e_mad = _mad([d["e2e"] for d in done]) * 1e3
        dominant = max(stage_mad, key=lambda k: stage_mad[k])
        if stage_mad[dominant] <= 0.0:
            dominant = None
        return {
            "frames": len(done),
            "e2e_mad_ms": round(e2e_mad, 4),
            "stage_mad_ms": {k: round(v, 4)
                             for k, v in stage_mad.items()},
            "dominant_stage": dominant,
            "dominant_share": round(stage_mad[dominant] / e2e_mad, 4)
            if dominant and e2e_mad > 0 else 0.0,
        }

    # -- export --------------------------------------------------------------
    def _us(self, t: float) -> float:
        return round((t - self.epoch) * 1e6, 3)

    def to_trace_ns(self, t: float) -> int:
        """A ``time.monotonic()`` instant in Unix-epoch nanoseconds, the
        clock of the profiler's device trace."""
        return self.clock[1] + int(round((t - self.clock[0]) * 1e9))

    def to_chrome(self) -> Dict[str, Any]:
        """Chrome trace-event JSON (Perfetto-loadable): named thread
        tracks, ``X`` slices with frame-seq args, flow events following
        each frame across tracks, async inflight-slot spans.

        Spans carrying an ``endpoint`` arg (the spliced remote-hop
        stages from obs/distributed) render under their own *process*
        track — pid 1 stays the local process, each distinct endpoint
        gets the next pid — and the per-frame flow chain crosses those
        process boundaries, so a distributed timeline loads as one
        flame graph instead of colliding tids."""
        recs = self._snapshot()
        pids: Dict[str, int] = {"": 1}
        tids: Dict[Tuple[int, str], int] = {}
        tid_next: Dict[int, int] = {}

        def _pid(endpoint: Optional[str]) -> int:
            key = str(endpoint) if endpoint else ""
            p = pids.get(key)
            if p is None:
                p = pids[key] = len(pids) + 1
            return p

        def _tid(pid: int, track: str) -> int:
            t = tids.get((pid, track))
            if t is None:
                t = tid_next.get(pid, 0) + 1
                tid_next[pid] = t
                tids[(pid, track)] = t
            return t

        events: List[dict] = []
        flows: Dict[int, List[Tuple[float, int, int]]] = {}
        for thread, kind, seq, t0, t1, track, args in recs:
            track = track or thread
            a: Dict[str, Any] = {"seq": seq}
            if args:
                a.update(args)
            pid = _pid(a.get("endpoint"))
            tid = _tid(pid, track)
            if t1 is None:
                events.append({"name": kind, "cat": "timeline",
                               "ph": "i", "s": "t", "ts": self._us(t0),
                               "pid": pid, "tid": tid, "args": a})
            else:
                events.append({"name": kind, "cat": "timeline",
                               "ph": "X", "ts": self._us(t0),
                               "dur": max(round((t1 - t0) * 1e6, 3), 0.0),
                               "pid": pid, "tid": tid, "args": a})
                if seq is not None:
                    flows.setdefault(seq, []).append((t0, pid, tid))
        # flow events: one arrow chain per frame across its tracks (and,
        # for hop spans, across endpoint processes) — the "follow this
        # frame" affordance in Perfetto
        for seq, hops in flows.items():
            if len(hops) < 2:
                continue
            hops.sort()
            for i, (t0, pid, tid) in enumerate(hops):
                ph = "s" if i == 0 else ("f" if i == len(hops) - 1 else "t")
                ev = {"name": "frame", "cat": "frame", "ph": ph,
                      "id": seq, "ts": self._us(t0), "pid": pid,
                      "tid": tid}
                if ph == "f":
                    ev["bp"] = "e"
                events.append(ev)
        for ph, name, aid, t, track in list(self._async):
            events.append({"name": name, "cat": "inflight", "ph": ph,
                           "id": aid, "ts": self._us(t), "pid": 1,
                           "tid": _tid(1, track)})
        meta: List[dict] = []
        for endpoint, pid in sorted(pids.items(), key=lambda kv: kv[1]):
            meta.append({"name": "process_name", "ph": "M", "pid": pid,
                         "args": {"name": "nnstreamer_tpu" if pid == 1
                                  else f"endpoint {endpoint}"}})
        for (pid, track), tid in sorted(tids.items(),
                                        key=lambda kv: (kv[0][0], kv[1])):
            meta.append({"name": "thread_name", "ph": "M", "pid": pid,
                         "tid": tid, "args": {"name": track}})
        # ``ts`` counts from ``epoch``; the clock pair lets a reader put
        # them on the device trace's clock (``device_trace`` does)
        return {"traceEvents": meta + events, "displayTimeUnit": "ms",
                "metadata": {"clock": {
                    "monotonic_s": self.clock[0], "unix_ns": self.clock[1],
                    "epoch_monotonic_s": self.epoch}}}

    def export_chrome(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)
