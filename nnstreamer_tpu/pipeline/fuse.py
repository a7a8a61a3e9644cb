"""Region fusion — compile chains of device-capable elements into ONE XLA
program.

The reference's per-element hot path is a C function call per element per
frame (tensor_filter.c:547, tensor_transform.c chain); cheap on a CPU, but
on a TPU every element-level dispatch is a host→device round trip. The
TPU-first answer (SURVEY §7 design stance: "the pipeline graph compiles
region-wise into jitted XLA programs") is this pass: after elements start,
maximal runs of *fusible* single-in/single-out elements are re-linked behind
a :class:`FusedRegion` whose chain performs a single ``jax.jit`` dispatch.
XLA then fuses the whole run — e.g. uint8 frame → normalize → MobileNet →
logits becomes one executable with one H2D transfer per frame.

An element opts in by implementing ``device_stage() -> DeviceStage | None``:
a pure, shape-polymorphic ``fn(consts, tensors) -> tensors`` plus the
device-resident constants (model params) passed as jit arguments (NOT
captured, so hot model reload swaps params without recompiling). Elements
whose per-frame behavior is host-side control flow (throttling drops, sync
policies, routing) simply don't implement it and stay unfused.

Disable globally with ``NNSTPU_FUSE=0`` or per-pipeline with
``Pipeline(fuse=False)``.
"""

from __future__ import annotations

import dataclasses
import os
import time as _time
import warnings
from typing import Any, Callable, List, Optional, Sequence, Tuple

from nnstreamer_tpu.log import get_logger
from nnstreamer_tpu.obs import timeline as _timeline
from nnstreamer_tpu.parallel import serve as _serve
from nnstreamer_tpu.pipeline import faults as _faults
from nnstreamer_tpu.pipeline.element import (
    CustomEvent,
    Element,
    Event,
    FlowError,
    Pad,
    peer_device_capable,
)
from nnstreamer_tpu.pipeline.supervise import effective_policy
from nnstreamer_tpu.tensors.buffer import (
    H2D_EXCLUSIVE_META,
    as_device_buffer,
    is_device_array,
)

log = get_logger("fuse")

# donation falls back gracefully where XLA can't apply it (host numpy
# inputs, backends without aliasing support): JAX executes correctly and
# warns — the warning is expected steady-state noise here, not a bug
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")
warnings.filterwarnings("ignore", message="Donation is not implemented")

#: error policies under which the supervisor may RE-INVOKE chain() with
#: the same buffer after a fault — a donated input can't be replayed, so
#: these arm a device-side replay copy instead of donating the original
_REPLAY_POLICIES = ("retry", "degrade")


@dataclasses.dataclass
class DeviceStage:
    """One element's contribution to a fused region.

    ``fn(consts, tensors)`` must be traceable by JAX (pure, no data-dependent
    Python control flow) and polymorphic over the number/shape of tensors.
    ``consts`` is any pytree (device arrays preferred); it is threaded
    through the jitted call as an argument so const updates (model reload)
    don't recompile when shapes are unchanged.

    ``key`` identifies the *traced computation* (not the consts): the region
    re-jits only when a member's key changes (model function swapped,
    transform option edited); a rebuild with identical keys just swaps
    consts into the existing executable — no XLA recompile.
    """

    consts: Any
    fn: Callable[[Any, List[Any]], List[Any]]
    key: Any = None
    #: serving-mesh spec (``parallel/serve.py`` grammar) this stage's
    #: consts are placed for — the region adopts it and compiles the
    #: whole-graph program sharded across the mesh. None = single device.
    mesh: Optional[str] = None
    #: optional deferred host completion ``fn(host_buf) -> TensorBuffer``
    #: attached to outgoing buffers (TensorBuffer.finalize) — used by
    #: decoders whose math runs on device but whose output needs host-only
    #: work (label strings, overlay compose). A finalizing stage terminates
    #: its fused run: downstream elements see its *device* tensors only
    #: after a sink materializes them.
    finalize: Optional[Callable] = None


def fusion_enabled() -> bool:
    return os.environ.get("NNSTPU_FUSE", "1").strip().lower() not in (
        "0", "false", "no", "off"
    )


def donation_enabled() -> bool:
    """Kill switch for input-slab donation (``NNSTPU_DONATE=0``): the
    fused program then never aliases its input buffers, which is the
    reference behavior for debugging donation-suspected corruption."""
    return os.environ.get("NNSTPU_DONATE", "1").strip().lower() not in (
        "0", "false", "no", "off"
    )


def _single_io(el: Element) -> bool:
    return len(el.sinkpads) == 1 and len(el.srcpads) == 1


def _stage_of(el: Element) -> Optional[DeviceStage]:
    getter = getattr(el, "device_stage", None)
    if getter is None:
        return None
    try:
        return getter()
    except Exception as e:  # noqa: BLE001 — an element that can't stage
        # simply stays unfused; fusion is an optimization, never a failure
        log.debug("element %s not fusible: %s", el.name, e)
        return None


def device_foldable(el: Element) -> bool:
    """Whether this element currently offers a device stage — i.e. whether
    ``fuse_pipeline`` could fold its per-frame math into a region's jitted
    program. The ingest lane planner (``pipeline/lanes.py``) consults this
    to report the device-side preprocessing preamble: a stage-capable
    ``tensor_transform`` adjacent to a filter runs inside the fused region
    (zero host math in the lanes) when fusion is on, and stays host-side
    lane work when it is off."""
    return _single_io(el) and _stage_of(el) is not None


class FusedRegion(Element):
    """Replaces a run of fusible elements with one jitted dispatch.

    The member elements stay in the pipeline (their properties, stats and
    custom-event handling remain live); only their pads are re-routed so
    buffers flow through this region instead. Caps negotiation chains the
    members' own ``transform_caps`` so negotiation semantics are identical
    to the unfused pipeline. Custom events are delivered into the member
    chain (internal links are kept); whatever the members do NOT consume
    reaches this region's internal return pad and is forwarded downstream —
    identical consume semantics to the unfused graph.
    """

    ELEMENT_NAME = "fused_region"
    #: a queue feeding a region may hand its whole backlog as one list —
    #: each buffer dispatches immediately (async), the dispatch window
    #: paces the batch, so a backlog becomes back-to-back device work
    HANDLES_LIST = True
    #: the jitted program consumes jax.Arrays directly — a DeviceBuffer
    #: input skips H2D staging and the ingest pool entirely
    DEVICE_PASSTHROUGH = True
    #: the jitted program may DONATE an incoming single-consumer payload
    #: (upload points mark those with H2D_EXCLUSIVE_META); chain() stages
    #: a replay copy whenever the original must survive a re-invoke
    DONATION_CONSUMER = True
    PROPERTIES = {**Element.PROPERTIES, "inflight": 2}

    def __init__(self, members: Sequence[Element], name=None, **props):
        super().__init__(name, **props)
        self.add_sink_pad("sink")
        self.add_src_pad("src")
        #: receives whatever flows out of the last member (events only —
        #: buffers no longer flow through members)
        self.internal_pad = self.add_sink_pad("fused-internal")
        self.members: List[Element] = list(members)
        #: (consts_list, jitted, finalize) — swapped atomically; readers
        #: take one local reference so invalidate() can never half-update it
        self._compiled: Optional[Tuple[list, Callable, Optional[Callable]]] \
            = None
        #: (keys_list, jitted) from the last trace — reused when a rebuild
        #: finds identical keys, so consts-only changes never recompile
        self._trace_cache: Optional[Tuple[list, Callable]] = None
        self._dead = False  # set when un-spliced back out of the graph
        self._verified = False  # first frame after a (re)compile is synced
        from nnstreamer_tpu.pipeline.dispatch import DispatchWindow

        #: bounded async dispatch: up to `inflight` outstanding batches
        #: (pipeline/dispatch.py); the region adopts the largest member
        #: `inflight` so `tensor_filter inflight=K` in a description
        #: keeps meaning after fusion
        member_inflight = [int(m.get_property("inflight"))
                           for m in self.members if "inflight" in m._props]
        if member_inflight:
            self._props["inflight"] = max(member_inflight)
        self._window = DispatchWindow(self)
        self._m_retrace = None  # region re-trace counter (lazy)
        self._m_whole = None    # whole-graph program gauge (lazy)
        self._donating = False  # the live jit was built with donation
        #: serving MeshPlan adopted from the members' mesh= specs (set by
        #: _build); None = single-device program
        self._mesh_plan = None

    # -- stage (re)build -----------------------------------------------------
    def _build(self) -> Tuple[list, Callable]:
        import jax

        stages = []
        for m in self.members:
            st = _stage_of(m)
            if st is None:
                raise FlowError(
                    f"fused region {self.name}: member {m.name} is no "
                    f"longer fusible"
                )
            stages.append(st)
        # mesh adoption: a member carrying a mesh= spec asks the WHOLE
        # region to compile sharded across that mesh. One program has one
        # mesh — mixed specs inside a run are a hard plan-time error (NOT
        # a FlowError: silently unsplicing to per-element dispatch would
        # hide a sharding contract violation)
        specs = sorted({st.mesh for st in stages if st.mesh is not None})
        if len(specs) > 1:
            raise _serve.MeshShardingError(
                f"fused region {self.name}: members carry mixed mesh specs "
                f"{specs}; align the mesh= properties or split the run "
                f"with a non-fusible element")
        plan = _serve.get_mesh_plan(specs[0]) \
            if specs and _serve.mesh_enabled() else None
        self._mesh_plan = plan
        stage_keys = [st.key for st in stages]
        # the mesh spec is part of the traced computation's identity: the
        # same member fns compile to a different XLA program per mesh
        keys = stage_keys + [("mesh", plan.spec if plan is not None else "")]
        cache = self._trace_cache
        # a None key means "cannot prove the computation is unchanged" —
        # never match it against the cache
        if any(k is None for k in stage_keys):
            cache = None
        if cache is not None and cache[0] == keys:
            jitted = cache[1]
        else:
            fns = [st.fn for st in stages]
            count = self._count_retrace

            def composed(consts, tensors):
                # the counter fires at TRACE time: jax.jit re-executes
                # this Python body once per distinct input signature, so
                # a new batch shape (aggregator flush tail vs full
                # window) is counted as the real XLA compile it is —
                # while the jit object below is REUSED across shapes, so
                # alternating batch sizes hit jit's per-shape executable
                # cache instead of retracing every frame
                count()
                with jax.named_scope("nns.fused"):
                    for f, c in zip(fns, consts):
                        tensors = f(c, list(tensors))
                return list(tensors)

            # donate the input tensor slab: the whole-graph program may
            # write its outputs into the (freshly uploaded, single-
            # consumer) input buffers instead of allocating, and the
            # dead inputs free at dispatch rather than at GC. chain()
            # substitutes a device-side replay copy whenever the
            # original must survive (unverified first frame, armed
            # retry/degrade policy, non-exclusive payload).
            # under a mesh plan this same jit IS the whole-graph SHARDED
            # program: chain() places inputs batch-sharded over dp
            # (serve.place_batch) and GSPMD propagates that sharding
            # through to the outputs — for leading-dim batch sharding
            # the propagation is exact, so the hand-off into a
            # downstream region on the same mesh is matched and moves
            # zero bytes. No sharding is CONSTRUCTED here (NNS117);
            # pinning out_shardings instead would reject the ragged
            # batches (flush tails) that place_batch runs replicated.
            jitted = jax.jit(composed, donate_argnums=(1,)) \
                if donation_enabled() else jax.jit(composed)
            self._trace_cache = (keys, jitted)
            self._donating = donation_enabled()
        compiled = ([st.consts for st in stages], jitted, stages[-1].finalize)
        self._compiled = compiled
        if self._m_whole is None:
            import weakref

            from nnstreamer_tpu.obs import get_registry

            ref = weakref.ref(self)

            def _whole() -> float:
                r = ref()
                return 1.0 if (r is not None and r._compiled is not None
                               and r._compiled[2] is not None) else 0.0

            self._m_whole = get_registry().gauge(
                "nns_fuse_whole_graph",
                "1 when this region's single jitted program covers the "
                "whole device-decodable graph (finalizing decoder stage "
                "folded in: no mid-stream D2H, host-only work deferred "
                "to the sink's fetch point)",
                fn=_whole, **self._obs_labels())
        self._verified = False  # first frame after (re)compile syncs
        return compiled

    def _count_retrace(self) -> None:
        """Count actual region re-traces (`nns_fuse_retraces_total`) —
        the no-new-XLA-recompiles acceptance gate reads this: a consts
        swap or an inflight change must NOT move it."""
        if self._m_retrace is None:
            from nnstreamer_tpu.obs import get_registry

            self._m_retrace = get_registry().counter(
                "nns_fuse_retraces_total",
                "Region re-traces (each implies one XLA compile)",
                **self._obs_labels())
        self._m_retrace.inc()

    def obs_snapshot(self):
        out = super().obs_snapshot()
        out.update(self._window.snapshot())
        if self._m_retrace is not None:
            out["retraces"] = int(self._m_retrace.value)
        return out

    def invalidate(self) -> None:
        """Drop the compiled (consts, jit) pair; the next frame re-pulls
        member stages. Whether that re-traces is decided by stage keys — a
        params-only model reload keeps the executable and just swaps consts;
        a swapped model function / edited transform option re-jits."""
        self._compiled = None

    def start(self):
        super().start()
        if self._dead:
            return
        # members were restarted (backends re-opened, possibly with changed
        # properties) — never reuse a program traced over the old backend
        self.invalidate()
        try:
            self._build()
        except FlowError:
            # a member stopped being fusible (properties changed while the
            # pipeline was NULL) — fall back to the original element links
            self.unsplice()

    # -- negotiation ---------------------------------------------------------
    def transform_caps(self, pad, caps):
        for m in self.members:
            out = m.transform_caps(m.sinkpads[0], caps)
            if out is None:
                return None
            caps = out
        return caps

    # -- hot path ------------------------------------------------------------
    def chain(self, pad, buf):
        if pad is self.internal_pad:
            raise FlowError(f"{self.name}: buffer on internal event pad")
        if self._qos_throttled():
            return None  # downstream-rate QoS drop (tensor_filter.c:426)
        fi = _faults.ACTIVE
        # the device span starts HERE, before the chaos hook: an injected
        # filter.invoke stall models a slow backend invoke, and the flight
        # recorder's variance attribution must see that time in the
        # "device" stage (the span ends before _window.admit so a full
        # window's fence shows up as fence_wait, not double-counted here)
        t_dev0 = _time.monotonic()
        if fi is not None:
            # chaos hook — the same `filter.invoke` site the unfused
            # filter checks (its chain doesn't run while fused), BEFORE
            # donation and the stash pop: a retrying error policy
            # re-enters chain with the buffer fully intact
            fi.check("filter.invoke",
                     seq=buf.meta.get(_timeline.TRACE_SEQ_META))
        compiled = self._compiled
        if compiled is None:
            try:
                compiled = self._build()
            except FlowError:
                # a member stopped being fusible mid-stream (e.g. throttle
                # enabled at runtime) — the unfused pipeline's behavior
                # resumes seamlessly
                return self._fallback(buf)
        consts, jitted, finalize = compiled
        from nnstreamer_tpu.pipeline.dispatch import POOL_STASH_META

        # upload points stamp single-consumer payloads; popped so the
        # marker never rides through to this region's OUTPUT buffer
        exclusive = bool(buf.meta.pop(H2D_EXCLUSIVE_META, False))
        stash = buf.meta.pop(POOL_STASH_META, None)
        args = list(buf.tensors)
        plan = self._mesh_plan
        t_sh1 = t_dev0
        if plan is not None:
            # mesh placement BEFORE the donation decision: an input
            # already carrying the plan's batch sharding (the matched
            # hand-off from an upstream sharded region) passes through
            # untouched — zero bytes, nns_reshard_bytes_total unmoved;
            # host arrays scatter over dp. Under a mesh the pre-dispatch
            # segment (placement, plus any injected invoke stall above)
            # attributes to the "shard" span, and "device" starts here —
            # the two stages still tile the frame's end-to-end time.
            args = [_serve.place_batch(t, plan) for t in args]
            t_sh1 = _time.monotonic()
        if self._donating and not (
                exclusive and self._verified
                and effective_policy(self) not in _REPLAY_POLICIES):
            # the jitted program donates (consumes) its input slab. Keep
            # the ORIGINALS alive by donating device-side replay copies
            # instead whenever the inputs may be touched again: an armed
            # retry/degrade policy re-invokes chain() with this same
            # buffer after a fault; an unverified first frame may fall
            # back to the member chain; a non-exclusive payload (source-
            # owned, tee'd) has readers this region can't see. Host
            # numpy inputs need no copy — XLA can't alias them, so
            # donation is a no-op for them.
            args = [t.copy() if is_device_array(t) else t for t in args]
        try:
            out = jitted(consts, args)
            if not self._verified:
                import jax
                # JAX dispatch is asynchronous: a data-dependent RUNTIME
                # failure would otherwise surface later at materialization
                # (sink to_host) as a pipeline error instead of here. Sync
                # the first frame after every (re)compile so both trace-time
                # and first-frame runtime failures take the fallback path;
                # steady-state frames stay fully async.
                # one-time post-(re)compile verification sync, not a
                # per-frame fence; steady-state frames skip this branch
                jax.block_until_ready(out)  # nns-lint: disable=NNS107 -- once
                self._verified = True
        except Exception as e:  # noqa: BLE001  # nns-lint: disable=NNS111 -- falls back to the member chain, whose error handling is authoritative
            # fusion is an optimization,
            # never a failure: a stage that won't trace or whose first
            # post-compile execution fails falls back to the member chain,
            # whose own error handling is authoritative. (Runtime failures
            # on later frames surface at materialization like any other
            # pipeline error.)
            log.warning("%s: fused program failed (%s); falling back to "
                        "member chain", self.name, e)
            return self._fallback(buf)
        tl = _timeline.ACTIVE
        if tl is not None:
            seq = buf.meta.get(_timeline.TRACE_SEQ_META)
            if seq is not None:
                if plan is not None:
                    tl.span("shard", seq, t_dev0, t_sh1, track=self.name)
                tl.span("device", seq, t_sh1, _time.monotonic(),
                        track=self.name)
        # bounded async dispatch: register the outstanding batch (fences
        # the OLDEST only when more than `inflight` are in flight); the
        # pooled host staging arrays this dispatch consumed recycle at
        # that fence point
        self._window.admit(out, stash)
        out_buf = buf.with_tensors(list(out))
        if plan is not None:
            # stamp which serving plan produced these (NamedSharding-
            # carrying) arrays — downstream consumers and dumps can read
            # the spec without touching the device data
            out_buf.meta[_serve.MESH_SPEC_META] = plan.spec
        if finalize is not None:
            out_buf = out_buf.replace(finalize=finalize)
        if peer_device_capable(self.srcpad):
            # downstream forwards resident buffers — emit a DeviceBuffer so
            # region→queue→region chains cross zero host copies (a
            # non-capable peer gets the plain buffer and materializes at
            # its own pace, exactly the pre-residency behavior)
            out_buf = as_device_buffer(out_buf)
        return self.srcpad.push(out_buf)

    def _fallback(self, buf):
        """Restore the original element links and replay ``buf`` (and all
        future buffers) through the member chain."""
        self.unsplice()
        first = self.members[0]
        return first._chain_entry(first.sinkpads[0], buf)

    def handle_eos(self):
        # EOS flush: every outstanding dispatch fences before EOS crosses
        # downstream — a sink observing EOS has all results materializable
        self._window.drain()

    def stop(self):
        self._window.drain()
        super().stop()

    # -- events --------------------------------------------------------------
    def src_event(self, pad: Pad, event: Event) -> None:
        from nnstreamer_tpu.pipeline.element import QosEvent

        if isinstance(event, QosEvent) and any(
                type(m).src_event is not Element.src_event
                for m in self.members):
            # a member consumes QoS (the filter): the event targets THIS
            # region's dispatch, since the members' chains don't run.
            # Deliver through the member chain too, so per-member QoS
            # state stays correct if the region later unsplices, and stop
            # — exactly one throttle gates the stream.
            self._qos_interval_s = event.target_interval_ns / 1e9
            last = self.members[-1]
            last._upstream_event_entry(last.srcpads[0], event)
            return
        # no consuming member: pass upstream past the region via the data
        # sink pad only (the base default would also loop the internal
        # pad, re-dispatching the event into the member chain)
        self.sinkpads[0].push_upstream_event(event)

    def sink_event(self, pad: Pad, event: Event) -> None:
        if pad is self.internal_pad:
            # an event the member chain chose to forward — pass it on
            self.srcpad.push_event(event)
            return
        if isinstance(event, CustomEvent):
            # deliver through the member chain; members that consume it
            # (e.g. tensor_filter eats reload_model) stop it there, others
            # forward it to the internal pad which sends it downstream
            self.members[0]._event_entry(self.members[0].sinkpads[0], event)
            self.invalidate()
            return
        from nnstreamer_tpu.pipeline.element import EosEvent

        if isinstance(event, EosEvent):
            # the internal event pad never sees EOS, so the base "all sink
            # pads at EOS" rule would deadlock — the data sink pad alone
            # decides here
            self.handle_eos()
            self.srcpad.push_event(event)
            return
        super().sink_event(pad, event)

    def __repr__(self):
        names = "+".join(m.name for m in self.members)
        return f"<FusedRegion [{names}]>"

    # -- splicing ------------------------------------------------------------
    def splice(self, pipe) -> None:
        self.pipeline = pipe
        for m in self.members:
            m._fused_region = self  # so member-level mutators (e.g.
            # TensorFilter.reload_model) can invalidate the compiled region
        first, last = self.members[0], self.members[-1]
        up_src = first.sinkpads[0].peer
        down_sink = last.srcpads[0].peer
        if up_src is not None:
            up_src.unlink()
            up_src.link(self.sinkpad)
        if down_sink is not None:
            last.srcpads[0].unlink()
            self.srcpad.link(down_sink)
        # route member-chain event outflow back through this region
        last.srcpads[0].link(self.internal_pad)
        log.info("fused region: %s", self)

    def unsplice(self) -> None:
        """Restore the original element links (region becomes inert)."""
        self._window.drain()  # outstanding dispatches belong to the dying
        # region; fence them so fallback replay can never reorder results
        first, last = self.members[0], self.members[-1]
        last.srcpads[0].unlink()  # internal pad
        up_src = self.sinkpad.peer
        down_sink = self.srcpad.peer
        if up_src is not None:
            up_src.unlink()
            up_src.link(first.sinkpads[0])
        if down_sink is not None:
            self.srcpad.unlink()
            last.srcpads[0].link(down_sink)
        for m in self.members:
            m._fused_region = None
        self._dead = True
        log.info("unspliced region: %s", self)


def fuse_pipeline(pipe) -> List[FusedRegion]:
    """Find maximal fusible runs and splice FusedRegions into the graph.

    Must run after non-source elements started (filter backends open their
    models in start(), and a backend is what makes a filter fusible) and
    before sources begin pushing.
    """
    regions: List[FusedRegion] = []
    in_run = set()
    stage_cache: dict = {}

    def stage_of(el):
        if id(el) not in stage_cache:
            stage_cache[id(el)] = _stage_of(el)
        return stage_cache[id(el)]

    for el in pipe.elements:
        if id(el) in in_run or not _single_io(el):
            continue
        head_stage = stage_of(el)
        if head_stage is None:
            continue
        up = el.sinkpads[0].peer.element if el.sinkpads[0].peer else None
        if up is not None and _single_io(up):
            up_stage = stage_of(up)
            # upstream fusible and able to extend → el is not a run head;
            # a finalizing upstream terminates its own run, so el IS a head
            if up_stage is not None and up_stage.finalize is None:
                continue
        run = [el]
        cur = el
        # a finalizing stage ends its run — nothing can fuse after it
        while stage_of(cur).finalize is None:
            peer = cur.srcpads[0].peer
            nxt = peer.element if peer else None
            if nxt is None or not _single_io(nxt) or stage_of(nxt) is None:
                break
            run.append(nxt)
            cur = nxt
        if len(run) < 2:
            continue
        for m in run:
            in_run.add(id(m))
        region = FusedRegion(run, name="+".join(m.name for m in run))
        region.splice(pipe)
        regions.append(region)
    return regions


# --------------------------------------------------------------------------
# plan-time matched-sharding verification (parallel/serve.py contract)
# --------------------------------------------------------------------------
def _element_mesh_plan(el):
    """The serving MeshPlan this element invokes under, or None. Covers
    sharded fused regions (``_mesh_plan`` from _build) and UNFUSED
    tensor_filters whose backend holds a plan (e.g. the budgeted-weights
    invoke path, which region fusion deliberately skips)."""
    plan = getattr(el, "_mesh_plan", None)
    if plan is None:
        plan = getattr(getattr(el, "fw", None), "_mesh_plan", None)
    return plan


def _element_mesh_spec(el) -> Optional[str]:
    plan = _element_mesh_plan(el)
    return plan.spec if plan is not None else None


def verify_mesh_boundaries(pipe) -> None:
    """PLAN-time check of the matched-sharding contract: every device-
    passthrough hand-off between two mesh-sharded invokers must carry
    identical mesh specs, so the producer's out-sharding equals the
    consumer's in-sharding and the hand-off moves ZERO bytes. A mismatch
    raises :class:`~nnstreamer_tpu.parallel.serve.MeshShardingError`
    before any frame flows — a silent runtime reshard of every frame is
    exactly the performance bug the ``mesh=`` property exists to prevent.
    (Hand-offs that cross a non-passthrough element materialize to host
    anyway and are exempt: that boundary's cost is already explicit.)

    Runs in ``Pipeline.start()`` after regions compile; inert when no
    element carries a mesh plan or ``NNSTPU_MESH=0``.
    """
    if not _serve.mesh_enabled():
        return
    producers = []
    for el in _live_invokers(pipe):
        spec = _element_mesh_spec(el)
        if spec is not None:
            producers.append((el, spec))
    for el, spec in producers:
        for pad in el.srcpads:
            _walk_boundary(el, spec, pad, set())


def announce_mesh_upstream(pipe) -> None:
    """Tell the H2D staging point that feeds each mesh-sharded invoker
    which plan its uploads must land on (``note_mesh_plan`` hook —
    ``queue prefetch-device=true``). A staged upload has ONE placement,
    so the plan travels upstream only along a path on which the meshed
    invoker is the sole consumer: the walk stops at a host boundary, at
    any other invoker (its parameters sit where IT placed them) and at
    any fan-out (a ``tee`` branch without the mesh would be handed a
    batch spread over devices its parameters are not on). Past those the
    upload stays on the default device and the sharded invoker re-places
    it itself, counted in ``nns_reshard_bytes_total``. Every hook is
    first reset to None so a restart under ``NNSTPU_MESH=0`` (or an
    edited ``mesh=``) never keeps a stale plan. Runs in
    ``Pipeline.start()`` next to :func:`verify_mesh_boundaries`."""
    for el in getattr(pipe, "elements", []):
        hook = getattr(el, "note_mesh_plan", None)
        if hook is not None:
            hook(None)
    if not _serve.mesh_enabled():
        return
    for el in _live_invokers(pipe):
        plan = _element_mesh_plan(el)
        if plan is not None:
            for pad in el.sinkpads:
                _announce_upstream(plan, pad, set())


def _places_own_params(el) -> bool:
    """A fused region or a tensor_filter: it runs a program against
    parameters it committed to devices itself."""
    return isinstance(el, FusedRegion) or hasattr(el, "fw")


def _announce_upstream(plan, pad: Pad, seen: set) -> None:
    peer = pad.peer
    if peer is None:
        return
    el = peer.element
    if id(el) in seen:
        return
    seen.add(id(el))
    if _places_own_params(el) or \
            not getattr(el, "DEVICE_PASSTHROUGH", False) or \
            sum(p.peer is not None for p in el.srcpads) > 1:
        return  # not ours alone to place: see announce_mesh_upstream
    hook = getattr(el, "note_mesh_plan", None)
    if hook is not None:
        hook(plan)
    for p in el.sinkpads:
        _announce_upstream(plan, p, seen)


def _live_invokers(pipe):
    """Pipeline elements buffers actually flow through: added elements
    minus fused members, plus the spliced regions themselves (regions
    live in ``pipe._regions``, not ``pipe.elements``)."""
    for el in getattr(pipe, "elements", []):
        if getattr(el, "_fused_region", None) is not None:
            continue  # fused member: its pads are re-routed
        yield el
    for r in (getattr(pipe, "_regions", None) or ()):
        if not getattr(r, "_dead", False):
            yield r


def pipeline_shard_count(pipe) -> int:
    """Largest serving-mesh fan-out any invoker in the pipeline runs
    under (1 = single device) — the SLO scheduler aligns its admission
    batch cap to a multiple of this so every admitted micro-batch splits
    evenly over dp shards."""
    n = 1
    for el in _live_invokers(pipe):
        plan = _element_mesh_plan(el)
        if plan is not None:
            n = max(n, int(plan.shard_count))
    return n


def _walk_boundary(producer, spec: str, pad: Pad, seen: set) -> None:
    peer = pad.peer
    if peer is None:
        return
    el = peer.element
    if id(el) in seen:
        return
    seen.add(id(el))
    consumer_spec = _element_mesh_spec(el)
    if consumer_spec is not None:
        if consumer_spec != spec:
            raise _serve.MeshShardingError(
                f"mesh boundary {producer.name} -> {el.name}: producer "
                f"shards over mesh={spec!r} but consumer expects "
                f"mesh={consumer_spec!r} — the hand-off would reshard "
                f"every frame; align the mesh= properties (or break "
                f"residency with a non-device-passthrough element to "
                f"make the host bounce explicit)")
        return  # matched; the consumer's own outputs get their own walk
    if not getattr(el, "DEVICE_PASSTHROUGH", False):
        return  # materializes to host — no device hand-off past here
    for p in el.srcpads:
        _walk_boundary(producer, spec, p, seen)
