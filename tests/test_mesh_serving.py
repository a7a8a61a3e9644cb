"""Mesh-sharded serving plane (parallel/serve.py + the `mesh=` element
property): spec grammar, plan caching, batch placement (zero-copy
matched hand-offs, counted reshards), the matched-sharding contract at
device-passthrough boundaries, SLO admission quantum alignment,
mesh-wide batch forming, per-shard HBM residency, and sharded
swap_model continuity.

Everything here runs on the 8-device virtual CPU mesh the test
conftest forces (--xla_force_host_platform_device_count=8) — the same
configuration the CI mesh smoke uses.
"""

import time

import numpy as np
import pytest

from nnstreamer_tpu import parse_launch
from nnstreamer_tpu.filters.jax_backend import (
    register_jax_model,
    unregister_jax_model,
)
from nnstreamer_tpu.obs import get_registry
from nnstreamer_tpu.obs import timeline as _timeline
from nnstreamer_tpu.parallel import serve
from nnstreamer_tpu.parallel.serve import (
    MeshPlan,
    MeshShardingError,
    canonical_spec,
    get_mesh_plan,
    parse_mesh_spec,
    place_batch,
)
from nnstreamer_tpu.serving.scheduler import SloScheduler
from nnstreamer_tpu.tensors import memory
from nnstreamer_tpu.tensors.buffer import TensorBuffer

jax = pytest.importorskip("jax")
jnp = pytest.importorskip("jax.numpy")


def _wait(cond, timeout=30.0, what="condition"):
    deadline = time.monotonic() + timeout
    while not cond():
        if time.monotonic() >= deadline:
            raise AssertionError(f"timed out waiting for {what}")
        time.sleep(0.01)


# -- spec grammar and plans ---------------------------------------------------


class TestMeshSpec:
    def test_parse_simple(self):
        assert parse_mesh_spec("dp4") == [("dp", 4)]
        assert parse_mesh_spec("dp2xtp2") == [("dp", 2), ("tp", 2)]

    def test_parse_wildcard(self):
        assert parse_mesh_spec("dp*") == [("dp", -1)]
        assert parse_mesh_spec("dp") == [("dp", -1)]  # bare axis = rest
        assert parse_mesh_spec("tp2xdp-1") == [("tp", 2), ("dp", -1)]

    @pytest.mark.parametrize("bad", ["", "qq4", "dp0", "4dp",
                                     "dp4q", "dp4xdp2"])
    def test_malformed_is_plan_time_error(self, bad):
        with pytest.raises(MeshShardingError):
            parse_mesh_spec(bad)

    def test_canonical(self):
        assert canonical_spec("DP8") == canonical_spec("dp8")

    def test_plan_cached_and_counts_shards(self):
        a = get_mesh_plan("dp8")
        b = get_mesh_plan("dp8")
        assert a is b, "plans must cache per canonical spec"
        assert a.shard_count == 8 and a.dp_size == 8
        mixed = get_mesh_plan("dp2xtp2")
        assert mixed.shard_count == 4 and mixed.dp_size == 2

    def test_sharding_for_ragged_batch_falls_back(self):
        plan = get_mesh_plan("dp8")
        full = np.zeros((8, 4), np.float32)
        ragged = np.zeros((3, 4), np.float32)
        assert plan.sharding_for(full) == plan.batched()
        assert plan.sharding_for(ragged) == plan.replicated()


# -- batch placement (the zero-copy contract) ---------------------------------


class TestPlaceBatch:
    def test_matched_device_array_moves_zero_bytes(self):
        plan = get_mesh_plan("dp8")
        x = np.ones((8, 4), np.float32)
        r0 = serve.reshard_bytes_total()
        placed = place_batch(x, plan)
        assert placed.sharding == plan.batched()
        again = place_batch(placed, plan)
        assert again is placed, "matched hand-off must be a no-op"
        assert serve.reshard_bytes_total() == r0, \
            "matched placements must not count as reshards"

    def test_mismatched_device_array_counts_reshard(self):
        plan8 = get_mesh_plan("dp8")
        plan2 = get_mesh_plan("dp2")
        x = place_batch(np.ones((8, 4), np.float32), plan8)
        r0 = serve.reshard_bytes_total()
        moved = place_batch(x, plan2)
        assert moved.sharding == plan2.batched()
        assert serve.reshard_bytes_total() == r0 + x.nbytes, \
            "a cross-mesh bounce must count its bytes"

    def test_ragged_batch_places_replicated(self):
        plan = get_mesh_plan("dp8")
        placed = place_batch(np.ones((3, 4), np.float32), plan)
        assert placed.sharding == plan.replicated()


# -- staged uploads land on the mesh (prefetch-device + mesh=) ----------------


STAGED_DESC = (
    "appsrc name=src ! "
    "queue name=stage max-size-buffers=4 prefetch-device=true ! "
    "tensor_transform mode=arithmetic option=typecast:float32,add:1.0 ! "
    "tensor_filter framework=jax model=mesh_sv_a name=fa {mesh}! "
    "queue max-size-buffers=8 materialize-host=true ! "
    "tensor_sink name=sink to-host=true"
)


class TestStagedUploadLandsOnTheMesh:
    """The flagship topology stages H2D in a ``prefetch-device`` queue
    ahead of the fused region. Under ``mesh=`` that upload must land
    batch-sharded on the mesh straight from the host — staged on the
    default device it made the region re-place every frame chip 0 →
    mesh, and nns_reshard_bytes_total counted every byte (PR 21)."""

    def _run(self, mesh, frames=6):
        pipe = parse_launch(STAGED_DESC.format(
            mesh=f"mesh={mesh} " if mesh else ""))
        src, sink = pipe.get("src"), pipe.get("sink")
        pipe.start()
        try:
            plan = pipe.get("stage")._upload_plan
            for i in range(frames):
                src.push([np.full((8, 4), i, np.uint8)])
            src.end_of_stream()
            msg = pipe.wait(timeout=120)
            assert msg is not None and msg.kind == "eos", msg
        finally:
            pipe.stop()
        return plan, [np.asarray(b.tensors[0]) for b in sink.buffers]

    def test_staged_frames_move_zero_reshard_bytes(self, chain_models):
        r0 = serve.reshard_bytes_total()
        plan, outs = self._run("dp8")
        assert plan is get_mesh_plan("dp8"), \
            "the staging queue never learned its consumer's mesh plan"
        assert serve.reshard_bytes_total() == r0, (
            "frames were staged on one device and resharded onto the "
            "mesh by the region")
        _, ref = self._run("")
        assert len(outs) == len(ref) == 6
        for o, r in zip(outs, ref):
            assert np.array_equal(o, r)

    def test_plan_is_dropped_when_the_mesh_is_switched_off(
            self, chain_models, monkeypatch):
        pipe = parse_launch(STAGED_DESC.format(mesh="mesh=dp8 "))
        pipe.start()
        try:
            assert pipe.get("stage")._upload_plan is get_mesh_plan("dp8")
        finally:
            pipe.stop()
        monkeypatch.setenv("NNSTPU_MESH", "0")
        pipe.start()
        try:
            assert pipe.get("stage")._upload_plan is None, \
                "a restart without the mesh kept the stale plan"
        finally:
            pipe.stop()

    # a staged upload has ONE placement, so the queue may take a plan
    # only when the meshed invoker is its sole consumer: past a fan-out
    # or another invoker the frames stay on the default device and the
    # meshed branch reshards them itself (counted), as before PR 21.
    # The models carry parameters: an unmeshed invoker commits them to
    # one device and XLA refuses a batch that arrives on eight.

    @pytest.fixture
    def param_models(self):
        register_jax_model("mesh_sv_pa", lambda p, x: (x * p,),
                           params=np.float32(2.0))
        register_jax_model("mesh_sv_pb", lambda p, x: (x + p,),
                           params=np.float32(1.0))
        yield
        unregister_jax_model("mesh_sv_pa")
        unregister_jax_model("mesh_sv_pb")

    def _run_desc(self, desc, sinks, frames=4):
        pipe = parse_launch(desc)
        src = pipe.get("src")
        pipe.start()
        try:
            plan = pipe.get("stage")._upload_plan
            for i in range(frames):
                src.push([np.full((8, 4), i, np.uint8)])
            src.end_of_stream()
            msg = pipe.wait(timeout=120)
            assert msg is not None and msg.kind == "eos", msg
        finally:
            pipe.stop()
        return plan, [[np.asarray(b.tensors[0])
                       for b in pipe.get(s).buffers] for s in sinks]

    @pytest.mark.parametrize("mesh_a,mesh_b", [
        ("mesh=dp8", ""),          # mixed: one branch meshed, one not
        ("mesh=dp8", "mesh=dp4"),  # mismatched meshes
        ("mesh=dp8", "mesh=dp8"),  # even matched: the walk stops at a tee
    ])
    def test_tee_branches_keep_the_upload_off_the_mesh(
            self, param_models, mesh_a, mesh_b):
        desc = (
            "appsrc name=src ! "
            "queue name=stage max-size-buffers=4 prefetch-device=true ! "
            "tee name=t "
            "t. ! tensor_transform mode=arithmetic "
            "option=typecast:float32,add:1.0 ! "
            f"tensor_filter framework=jax model=mesh_sv_pa {mesh_a} ! "
            "tensor_sink name=sa to-host=true "
            "t. ! tensor_transform mode=arithmetic "
            "option=typecast:float32,add:1.0 ! "
            f"tensor_filter framework=jax model=mesh_sv_pb {mesh_b} ! "
            "tensor_sink name=sb to-host=true")
        plan, (a, b) = self._run_desc(desc, ("sa", "sb"))
        assert plan is None, "a fan-out's upload was placed for one branch"
        assert len(a) == len(b) == 4
        for i in range(4):
            assert np.array_equal(a[i], np.full((8, 4), (i + 1) * 2.0))
            assert np.array_equal(b[i], np.full((8, 4), i + 2.0))

    def test_unmeshed_invoker_in_between_keeps_its_own_placement(
            self, param_models):
        desc = (
            "appsrc name=src ! "
            "queue name=stage max-size-buffers=4 prefetch-device=true ! "
            "tensor_transform mode=arithmetic "
            "option=typecast:float32,add:1.0 ! "
            "tensor_filter framework=jax model=mesh_sv_pa ! "
            "queue max-size-buffers=4 ! "
            "tensor_filter framework=jax model=mesh_sv_pb mesh=dp8 ! "
            "tensor_sink name=sink to-host=true")
        plan, (outs,) = self._run_desc(desc, ("sink",))
        assert plan is None, \
            "the plan walked through an invoker that is not on the mesh"
        assert len(outs) == 4
        for i, o in enumerate(outs):
            assert np.array_equal(o, np.full((8, 4), (i + 1) * 2.0 + 1.0))

    def test_to_device_takes_a_per_tensor_sharding_callable(self):
        plan = get_mesh_plan("dp8")
        buf = TensorBuffer([np.zeros((8, 4), np.float32),
                            np.zeros((3, 4), np.float32)])
        dev = buf.to_device(sharding=plan.sharding_for)
        assert dev.tensors[0].sharding == plan.batched()
        assert dev.tensors[1].sharding == plan.replicated()  # ragged


# -- chained sharded regions: matched boundaries ------------------------------


@pytest.fixture
def chain_models():
    register_jax_model("mesh_sv_a", lambda x: (x * 2.0,))
    register_jax_model("mesh_sv_b", lambda x: (x + 1.0,))
    yield "mesh_sv_a", "mesh_sv_b"
    unregister_jax_model("mesh_sv_a")
    unregister_jax_model("mesh_sv_b")


CHAIN_DESC = (
    "appsrc name=src ! "
    "tensor_filter framework=jax model=mesh_sv_a name=fa mesh=dp8 ! "
    "queue max-size-buffers=4 ! "
    "tensor_filter framework=jax model=mesh_sv_b name=fb mesh=dp8 ! "
    "tensor_sink name=sink to-host=true"
)


class TestChainedShardedRegions:
    def _run(self, desc, frames=4):
        pipe = parse_launch(desc)
        src, sink = pipe.get("src"), pipe.get("sink")
        pipe.start()
        try:
            for i in range(frames):
                src.push([np.full((8, 4), float(i), np.float32)])
            src.end_of_stream()
            msg = pipe.wait(timeout=120)
            assert msg is not None and msg.kind == "eos", msg
        finally:
            pipe.stop()
        return pipe, [np.asarray(b.tensors[0]) for b in sink.buffers]

    def test_zero_reshard_across_matched_boundary(self, chain_models):
        r0 = serve.reshard_bytes_total()
        pipe, outs = self._run(CHAIN_DESC)
        assert len(outs) == 4
        for i, o in enumerate(outs):
            assert np.array_equal(o, np.full((8, 4), i * 2.0 + 1.0,
                                             np.float32))
        assert serve.reshard_bytes_total() == r0, (
            "two chained dp8 regions must hand the batch off without "
            "moving a byte")

    def test_shard_count_gauge_and_meta_stamp(self, chain_models):
        pipe, _ = self._run(CHAIN_DESC)
        g = get_registry().get("nns_shard_count",
                               pipeline=pipe.name, filter="fa")
        assert g is not None and float(g.value) == 8.0
        last = pipe.get("sink").buffers[-1]
        assert last.meta.get(serve.MESH_SPEC_META) == "dp8", \
            "sharded region output must carry its mesh-spec meta"

    def test_shard_span_recorded(self, chain_models):
        tl = _timeline.activate()
        try:
            self._run(CHAIN_DESC)
            names = {ev["name"] for ev in tl.to_chrome()["traceEvents"]}
        finally:
            _timeline.deactivate()
        assert "shard" in names, \
            "the placement wait must surface as its own ledger stage"

    def test_mismatched_boundary_is_plan_time_error(self, chain_models):
        desc = CHAIN_DESC.replace("model=mesh_sv_b name=fb mesh=dp8",
                                  "model=mesh_sv_b name=fb mesh=dp2xtp2")
        pipe = parse_launch(desc)
        try:
            with pytest.raises(MeshShardingError, match="fa.*fb|reshard"):
                pipe.start()
        finally:
            pipe.stop()

    def test_mixed_specs_in_one_region_rejected(self, chain_models):
        desc = CHAIN_DESC.replace("queue max-size-buffers=4 ! ", "")
        desc = desc.replace("model=mesh_sv_b name=fb mesh=dp8",
                            "model=mesh_sv_b name=fb mesh=dp4")
        pipe = parse_launch(desc)
        try:
            with pytest.raises(MeshShardingError):
                pipe.start()
        finally:
            pipe.stop()


# -- admission quantum + mesh-wide batch forming ------------------------------


class TestMeshQuantum:
    def test_scheduler_batch_cap_rounds_to_quantum(self):
        sched = SloScheduler(budget_ms=50.0)
        sched.note_mesh(8)
        sched.controller.batch_cap = 21
        assert sched.batch_cap() == 16, "cap rounds DOWN to a dp multiple"
        sched.controller.batch_cap = 3
        assert sched.batch_cap() == 8, "cap never rounds below one window"
        assert sched.snapshot()["mesh_quantum"] == 8

    def test_scheduler_quantum_one_is_identity(self):
        sched = SloScheduler(budget_ms=50.0)
        cap = sched.batch_cap()
        sched.note_mesh(1)
        assert sched.batch_cap() == cap

    def test_aggregator_rounds_frames_out_up(self):
        from nnstreamer_tpu.elements.aggregator import TensorAggregator

        agg = TensorAggregator("agg", frames_out=12)
        agg.note_mesh_quantum(8)
        assert int(agg.get_property("frames_out")) == 16
        agg.note_mesh_quantum(8)  # idempotent once aligned
        assert int(agg.get_property("frames_out")) == 16

    def test_aggregator_passthrough_untouched(self):
        from nnstreamer_tpu.elements.aggregator import TensorAggregator

        agg = TensorAggregator("agg", frames_out=1)
        agg.note_mesh_quantum(8)
        assert int(agg.get_property("frames_out")) == 1, \
            "per-frame service must stay per-frame"

    def test_pipeline_start_aligns_batch_former(self, chain_models):
        pipe = parse_launch(
            "appsrc name=src ! "
            "tensor_aggregator name=agg frames-in=1 frames-out=6 "
            "frames-dim=1 concat=true ! "
            "tensor_filter framework=jax model=mesh_sv_a mesh=dp8 ! "
            "tensor_sink name=sink to-host=true")
        src = pipe.get("src")
        pipe.start()
        try:
            assert int(pipe.get("agg").get_property("frames_out")) == 8, \
                "start() must round the former's window to the dp fan-out"
            for i in range(8):
                src.push([np.full((1, 4), float(i), np.float32)])
            src.end_of_stream()
            msg = pipe.wait(timeout=120)
            assert msg is not None and msg.kind == "eos", msg
        finally:
            pipe.stop()
        outs = [np.asarray(b.tensors[0])
                for b in pipe.get("sink").buffers]
        assert len(outs) == 1 and outs[0].shape == (8, 4)
        assert np.array_equal(
            outs[0], np.arange(8, dtype=np.float32)[:, None]
            .repeat(4, 1) * 2.0)


# -- per-shard HBM residency + sharded swap continuity ------------------------


@pytest.fixture(autouse=True)
def _clean_accountant():
    memory.deactivate()
    yield
    memory.deactivate()


class TestPerShardResidency:
    SHAPE = (64, 64)

    def _register(self, name, scale):
        w = jnp.ones(self.SHAPE, jnp.float32) * scale
        register_jax_model(
            name, lambda p, x: (x.astype(jnp.float32) * p["w"][0, 0],),
            {"w": w})
        return int(np.prod(self.SHAPE)) * 4

    def test_weights_account_once_per_shard(self):
        nbytes = self._register("mesh_sv_w", 2.0)
        try:
            acct = memory.activate(64 * nbytes)
            pipe = parse_launch(
                "appsrc name=src ! tensor_filter framework=jax "
                "model=mesh_sv_w name=filter mesh=dp8 ! "
                "tensor_sink name=sink to-host=true")
            src, sink = pipe.get("src"), pipe.get("sink")
            pipe.start()
            try:
                src.push([np.full((8, 4), 1.0, np.float32)])
                _wait(lambda: len(sink.buffers) >= 1, what="warm frame")
                assert acct._used.get("weights", 0) == 8 * nbytes, (
                    "a replicated dp8 placement is a full weight copy "
                    "per chip — nns_mem_used_bytes must count all 8")
                shard_keys = [k for k in acct.residency._units
                              if ":shard" in k]
                assert len(shard_keys) == 8
                src.end_of_stream()
                msg = pipe.wait(timeout=120)
                assert msg is not None and msg.kind == "eos", msg
            finally:
                pipe.stop()
        finally:
            unregister_jax_model("mesh_sv_w")

    def test_sharded_swap_retires_group_one_rejit_zero_drops(self):
        nbytes = self._register("mesh_sv_w", 2.0)
        try:
            acct = memory.activate(64 * nbytes)
            pipe = parse_launch(
                "appsrc name=src ! tensor_filter framework=jax "
                "model=mesh_sv_w name=filter mesh=dp8 ! "
                "tensor_sink name=sink to-host=true")
            src, sink = pipe.get("src"), pipe.get("sink")
            pipe.start()
            try:
                for i in range(5):
                    src.push([np.full((8, 4), float(i), np.float32)])
                _wait(lambda: len(sink.buffers) >= 5, what="first 5")
                used_before = acct.used_bytes()
                keys_before = {k for k in acct.residency._units
                               if ":shard" in k}
                assert len(keys_before) == 8

                new = {"w": jnp.ones(self.SHAPE, jnp.float32) * 5.0}
                report = pipe.swap_model("filter", weights=new)

                assert acct.used_bytes() == used_before, \
                    "per-shard swap must retire the whole old group"
                keys_after = {k for k in acct.residency._units
                              if ":shard" in k}
                assert len(keys_after) == 8
                assert keys_before.isdisjoint(keys_after)
                assert all(":e1:" in k for k in keys_after), \
                    "new group must be keyed by the bumped epoch"
                assert report["residency_unit"].endswith(":e1")

                src.push([np.full((8, 4), 1.0, np.float32)])
                _wait(lambda: len(sink.buffers) >= 6, what="post-swap")
                fw = pipe.get("filter").fw
                jitted_after_swap = fw._jitted
                assert jitted_after_swap is not None
                for i in range(4):
                    src.push([np.full((8, 4), float(i), np.float32)])
                src.end_of_stream()
                msg = pipe.wait(timeout=120)
                assert msg is not None and msg.kind == "eos", msg
                assert fw._jitted is jitted_after_swap, (
                    "a params-only sharded swap re-jits exactly once, "
                    "not per frame")
            finally:
                pipe.stop()
            outs = [np.asarray(b.tensors[0]) for b in sink.buffers]
            assert len(outs) == 10, "swap dropped frames"
            for i in range(5):  # old epoch: x * 2
                assert np.array_equal(
                    outs[i], np.full((8, 4), i * 2.0, np.float32))
            assert np.array_equal(outs[5],
                                  np.full((8, 4), 5.0, np.float32))
            for i, o in enumerate(outs[6:]):  # new epoch: x * 5
                assert np.array_equal(
                    o, np.full((8, 4), i * 5.0, np.float32))
        finally:
            unregister_jax_model("mesh_sv_w")


class TestPlacementAccounting:
    def test_place_params_registers_pinned_bytes(self):
        from jax.sharding import PartitionSpec as P

        from nnstreamer_tpu.parallel.mesh import make_mesh

        acct = memory.activate(1 << 30)
        mesh = make_mesh([("dp", 8)])
        params = {"w": np.ones((16, 16), np.float32)}
        placed = serve.place_params(params, mesh, {"w": P()},
                                    label="test:pinned")
        used = acct._used.get("weights", 0)
        assert used >= 8 * params["w"].nbytes, (
            "a replicated placement occupies every chip; the accountant "
            "must see the full multi-chip footprint")
        pinned = [u for u in acct.residency.snapshot()["units"]
                  if u["pinned"]]
        assert pinned, "external placements adopt as pinned units"
        del placed
        import gc

        gc.collect()
        assert acct._used.get("weights", 0) < used, \
            "dropping the placement must release its adopted bytes"
