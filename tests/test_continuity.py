"""Serving continuity (pipeline/continuity.py): zero-downtime model
swap, checkpoint/restore, and the persistent compile cache.

The contract under test, per docs/robustness.md "Serving continuity":

- ``swap_model`` drops zero frames, produces byte-identical output on
  each side of the cutover, invalidates the owning fused region exactly
  once, and composes with an active fault injector + retry policy;
- a weights-only swap re-registers the HBM residency unit under the new
  epoch key and retires the old one in the same step — no
  ``nns_mem_used_bytes`` leak, no stale unit;
- every checkpointable component (repo slots, scheduler EWMAs/knobs,
  P2 markers, flight ledger, dedup windows, residency LRU) round-trips
  through its snapshot/restore pair, including under injected faults;
- ``NNSTPU_CHECKPOINT`` / ``JAX_COMPILATION_CACHE_DIR`` unset means none
  of this code runs (byte-identical serving path, no files written);
- the compile cache lives where ONE rule says: the directory
  ``JAX_COMPILATION_CACHE_DIR`` names (JAX's own handling, never
  overridden), else ``<checkout>/.jax_cache``;
- the persistent compile cache serves re-traces from disk: after
  ``jax.clear_caches()`` the same program loads with zero new XLA
  compiles, visible in ``nns_compile_cache_hits_total``.
"""

import os
import time

import numpy as np
import pytest

from nnstreamer_tpu import parse_launch
from nnstreamer_tpu.elements.repo import GLOBAL_REPO, TensorRepo
from nnstreamer_tpu.filters.jax_backend import (
    register_jax_model,
    unregister_jax_model,
)
from nnstreamer_tpu.obs import get_registry
from nnstreamer_tpu.obs.flight import FlightRecorder
from nnstreamer_tpu.obs.quantiles import P2Quantile
from nnstreamer_tpu.pipeline import continuity, faults
from nnstreamer_tpu.query.resilience import DedupWindow, NEW, PENDING
from nnstreamer_tpu.serving.scheduler import (
    FeedbackController,
    ServiceRateEstimator,
    SloScheduler,
)
from nnstreamer_tpu.tensors import memory
from nnstreamer_tpu.tensors.buffer import TensorBuffer

jnp = pytest.importorskip("jax.numpy")


@pytest.fixture(autouse=True)
def _clean_injectors():
    faults.deactivate()
    memory.deactivate()
    yield
    faults.deactivate()
    memory.deactivate()


@pytest.fixture(autouse=True)
def cache_env(tmp_path, monkeypatch):
    """What ``JAX_COMPILATION_CACHE_DIR=<dir>`` gives a fresh process: the
    variable set AND ``jax.config`` carrying it. JAX reads the variable
    at import and this process imported jax long ago, so the fixture
    does JAX's half by hand (the subprocess test below checks the real
    thing). Autouse, so every test that arms the cache — directly or
    through a checkpoint dir — stays out of ``<checkout>/.jax_cache``."""
    import jax
    from jax._src import compilation_cache

    directory = str(tmp_path / "jax-cache")
    compilation_cache.reset_cache()
    monkeypatch.setenv(continuity.JAX_CACHE_ENV, directory)
    monkeypatch.setattr(continuity, "_cache_dir", None)
    before = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", directory)
    yield directory
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


def _cval(name, **labels):
    m = get_registry().get(name, **labels)
    return 0.0 if m is None else m.value


def _wait(cond, timeout=30.0, what="condition"):
    deadline = time.monotonic() + timeout
    while not cond():
        if time.monotonic() >= deadline:
            raise AssertionError(f"timed out waiting for {what}")
        time.sleep(0.01)


# -- live model swap ----------------------------------------------------------


@pytest.fixture
def swap_models():
    register_jax_model("cont_a", lambda x: x + 1.0)
    register_jax_model("cont_b", lambda x: x * 3.0)
    yield "cont_a", "cont_b"
    unregister_jax_model("cont_a")
    unregister_jax_model("cont_b")


SWAP_DESC = (
    "appsrc name=src ! "
    "tensor_transform mode=arithmetic option=typecast:float32,add:0.0 ! "
    "tensor_filter framework=jax model=cont_a name=filter "
    "is-updatable=true ! tensor_sink name=sink"
)

FRAMES = [np.full((4,), float(i), np.float32) for i in range(10)]


def _run_with_swap(desc, error_policy=None):
    """Push 5 frames, fence on their arrival, swap to cont_b, push 5
    more. The pre-arrival wait makes the cutover seq deterministic so
    byte-identity per side is assertable."""
    kw = {"error_policy": error_policy} if error_policy else {}
    pipe = parse_launch(desc, **kw)
    src, sink = pipe.get("src"), pipe.get("sink")
    pipe.start()
    try:
        for f in FRAMES[:5]:
            src.push([f.copy()])
        _wait(lambda: len(sink.buffers) >= 5, what="first 5 frames")
        report = pipe.swap_model("filter", model="cont_b")
        for f in FRAMES[5:]:
            src.push([f.copy()])
        src.end_of_stream()
        msg = pipe.wait(timeout=60)
        assert msg is not None and msg.kind == "eos", msg
    finally:
        pipe.stop()
    outs = [np.asarray(b.tensors[0]) for b in sink.buffers]
    return pipe, report, outs


class TestSwapModel:
    def test_zero_drop_byte_identical_each_side(self, swap_models):
        swaps0 = _cval("nns_model_swaps_total")
        pipe, report, outs = _run_with_swap(SWAP_DESC)
        assert len(outs) == len(FRAMES), "swap dropped frames"
        for i in range(5):  # old epoch: x + 1
            assert np.array_equal(outs[i], FRAMES[i] + 1.0), f"frame {i}"
        for i in range(5, 10):  # new epoch: x * 3
            assert np.array_equal(outs[i], FRAMES[i] * 3.0), f"frame {i}"
        assert report["epoch"] == 1
        assert report["invalidations"] == 1, \
            "the owning fused region must invalidate exactly once"
        assert _cval("nns_model_swaps_total") == swaps0 + 1

    def test_swap_composes_with_retry_policy(self, swap_models):
        inj = faults.activate("filter.invoke:rate=0.3", seed=11)
        _, report, outs = _run_with_swap(SWAP_DESC, error_policy="retry")
        assert inj.injected("filter.invoke") > 0, "no fault ever fired"
        assert len(outs) == len(FRAMES), "retry + swap lost frames"
        for i in range(5):
            assert np.array_equal(outs[i], FRAMES[i] + 1.0), f"frame {i}"
        for i in range(5, 10):
            assert np.array_equal(outs[i], FRAMES[i] * 3.0), f"frame {i}"
        assert report["invalidations"] == 1

    def test_second_swap_bumps_epoch(self, swap_models):
        pipe = parse_launch(SWAP_DESC)
        pipe.start()
        try:
            r1 = pipe.swap_model("filter", model="cont_b")
            r2 = pipe.swap_model("filter", model="cont_a")
        finally:
            pipe.stop()
        assert (r1["epoch"], r2["epoch"]) == (1, 2)

    def test_bad_arguments_raise(self, swap_models):
        pipe = parse_launch(SWAP_DESC)
        pipe.start()
        try:
            with pytest.raises(ValueError, match="need model"):
                pipe.swap_model("filter")
            with pytest.raises(KeyError, match="no element"):
                pipe.swap_model("nope", model="cont_b")
            with pytest.raises(TypeError, match="not a tensor_filter"):
                pipe.swap_model("sink", model="cont_b")
        finally:
            pipe.stop()


# -- weights swap under an HBM budget (residency epoch accounting) ------------


class TestWeightsSwapResidency:
    SHAPE = (64, 64)

    def _register(self):
        ballast = jnp.ones(self.SHAPE, jnp.float32) * 2.0
        register_jax_model(
            "cont_w", lambda p, x: (x.astype(jnp.float32) * p["w"][0, 0],),
            {"w": ballast})
        return int(np.prod(self.SHAPE)) * 4

    def test_swap_retires_old_unit_no_leak(self, swap_models):
        nbytes = self._register()
        try:
            acct = memory.activate(4 * nbytes)
            pipe = parse_launch(
                "appsrc name=src ! tensor_filter framework=jax "
                "model=cont_w name=filter ! tensor_sink name=sink")
            src, sink = pipe.get("src"), pipe.get("sink")
            pipe.start()
            try:
                src.push([np.full((4,), 1.0, np.float32)])
                _wait(lambda: len(sink.buffers) >= 1, what="warmup frame")
                assert np.allclose(np.asarray(sink.buffers[0].tensors[0]),
                                   2.0)
                used_before = acct.used_bytes()
                keys_before = set(acct.residency._units.keys())

                new = {"w": jnp.ones(self.SHAPE, jnp.float32) * 5.0}
                report = pipe.swap_model("filter", weights=new)

                # the old epoch's unit retired in the same step — a swap
                # must not leak nns_mem_used_bytes
                assert acct.used_bytes() == used_before
                keys_after = set(acct.residency._units.keys())
                assert report["retired_unit"] in keys_before
                assert report["retired_unit"] not in keys_after
                assert report["residency_unit"] in keys_after
                assert report["residency_unit"].endswith(":e1")

                src.push([np.full((4,), 1.0, np.float32)])
                src.end_of_stream()
                msg = pipe.wait(timeout=60)
                assert msg is not None and msg.kind == "eos", msg
                assert np.allclose(np.asarray(sink.buffers[1].tensors[0]),
                                   5.0), "new weights never took effect"
            finally:
                pipe.stop()
        finally:
            unregister_jax_model("cont_w")


# -- component state round-trips ----------------------------------------------


class TestStateRoundTrips:
    def test_p2_quantile(self):
        q = P2Quantile(0.99)
        for i in range(200):
            q.observe(float(i % 37))
        clone = P2Quantile(0.99)
        clone.restore(q.snapshot())
        assert clone.quantile() == q.quantile()
        clone.observe(1000.0)  # restored markers keep streaming

    def test_service_rate_estimator(self):
        est = ServiceRateEstimator()
        for i in range(10):
            est.observe_invoke(0.004)
            est.observe_completion(now=float(i) * 0.01)
        clone = ServiceRateEstimator()
        clone.restore(est.snapshot())
        assert clone.snapshot() == est.snapshot()
        assert clone.service_time_s() == est.service_time_s()

    def test_slo_scheduler_round_trip(self):
        sched = SloScheduler(budget_ms=50.0, name="cont-rt")
        for _ in range(20):
            sched.estimator.observe_invoke(0.004)
            sched.controller.record_completion(0.01)
        state = sched.checkpoint_state()
        clone = SloScheduler(budget_ms=50.0, name="cont-rt2")
        clone.restore_state(state)
        assert clone.estimator.snapshot() == sched.estimator.snapshot()
        got = clone.controller.snapshot()
        want = sched.controller.snapshot()
        assert got["batch_cap"] == want["batch_cap"]
        assert got["inflight"] == want["inflight"]
        assert clone._lanes_hint >= sched._lanes_hint

    def test_flight_recorder_round_trip(self):
        fr = FlightRecorder(dump_dir=None, min_samples=5)
        for seq in range(12):
            t = float(seq)
            fr.span("device", seq, t, t + 0.002)
            fr.span("sink", seq, t + 0.002, t + 0.004, e2e_s=0.004)
        state = fr.checkpoint_state()
        clone = FlightRecorder(dump_dir=None, min_samples=5)
        clone.restore_state(state)
        assert clone.checkpoint_state()["completed"] == \
            state["completed"]
        assert clone.slo_snapshot() == fr.slo_snapshot()
        assert clone.attribution() == fr.attribution()

    def test_dedup_window_round_trip_drops_pending(self):
        w = DedupWindow(size=8)
        assert w.admit(1) is NEW
        w.resolve(1, ("reply", b"one"))
        assert w.admit(2) is NEW  # left PENDING on purpose
        clone = DedupWindow(size=8)
        clone.restore(w.snapshot())
        # the resolved id replays from the restored window...
        assert clone.admit(1) == ("reply", b"one")
        # ...but the in-flight one was dropped (its invocation died with
        # the old process), so the resend re-invokes
        assert clone.admit(2) is NEW

    def test_residency_lru_order_restored_by_label(self):
        acct = memory.activate(1 << 20)
        res = acct.residency
        units = {}
        for name in ("ua", "ub", "uc"):
            units[name] = res.register(
                key=f"k:{name}", host_value=np.zeros(4),
                nbytes=16, loader=lambda h: h, label=name)
        units["ua"].value()  # LRU touch: order becomes ub, uc, ua
        state = res.checkpoint_state()
        assert state["lru"] == ["ub", "uc", "ua"]

        memory.deactivate()
        acct2 = memory.activate(1 << 20)
        res2 = acct2.residency
        # a restarted process re-registers under NEW keys (id()-based);
        # labels are the stable identity the LRU order restores by
        for name in ("ua", "ub", "uc"):
            res2.register(key=f"k2:{name}", host_value=np.zeros(4),
                          nbytes=16, loader=lambda h: h, label=name)
        res2.restore_state(state)
        assert [u.label for u in res2._units.values()] == \
            ["ub", "uc", "ua"]


# -- tensor_repo slots under injected faults (satellite: repo coverage) -------


class TestRepoCheckpoint:
    def test_slot_snapshot_restore_round_trip(self):
        repo = TensorRepo()
        repo.set("slot0", TensorBuffer([np.arange(6, dtype=np.float32)]))
        repo.set("slot1", TensorBuffer([np.ones((2, 3), np.int32)]))
        state = repo.snapshot()
        clone = TensorRepo()
        clone.restore(state)
        for slot in ("slot0", "slot1"):
            a = repo.peek(slot).tensors[0]
            b = clone.peek(slot).tensors[0]
            assert np.array_equal(np.asarray(a), np.asarray(b))
        assert clone.get("slot0", consume=True) is not None
        assert clone.peek("slot0") is None  # consume still works

    def test_snapshot_is_host_side_copy(self):
        repo = TensorRepo()
        arr = np.arange(4, dtype=np.float32)
        repo.set("s", TensorBuffer([arr]))
        state = repo.snapshot()
        arr += 100.0  # mutating the live buffer after the snapshot...
        clone = TensorRepo()
        clone.restore(state)
        # ...must not corrupt the checkpoint (np.asarray of a host
        # ndarray aliases, so this documents the aliasing boundary:
        # restore happens in a NEW process in real use)
        assert clone.peek("s") is not None

    def test_repo_pipeline_survives_faults_then_checkpoints(self,
                                                            swap_models):
        """A repo-backed recurrent loop keeps its slot through injected
        filter faults + retry, and the surviving slot checkpoints."""
        inj = faults.activate("filter.invoke:rate=0.3", seed=3)
        desc = ("appsrc name=src ! tensor_filter framework=jax "
                "model=cont_a name=f ! tee name=t ! queue ! "
                "tensor_sink name=sink  "
                "t. ! queue ! tensor_reposink slot=77")
        pipe = parse_launch(desc, error_policy="retry")
        src, sink = pipe.get("src"), pipe.get("sink")
        pipe.start()
        try:
            for f in FRAMES[:6]:
                src.push([f.copy()])
            src.end_of_stream()
            msg = pipe.wait(timeout=60)
            assert msg is not None and msg.kind == "eos", msg
        finally:
            pipe.stop()
        assert inj.injected("filter.invoke") > 0, "no fault ever fired"
        assert len(sink.buffers) == 6, "retry lost frames"
        state = GLOBAL_REPO.snapshot()
        try:
            assert "77" in state, f"slot missing from snapshot: {state.keys()}"
            # the slot holds the LAST processed frame, byte-exact
            assert np.array_equal(state["77"][0], FRAMES[5] + 1.0)
        finally:
            GLOBAL_REPO.remove("77")


# -- pipeline checkpoint / restore end-to-end ---------------------------------


class TestPipelineCheckpointRestore:
    DESC = ("videotestsrc num-buffers=8 ! "
            "tensor_converter ! queue slo-budget-ms=100 ! "
            "tensor_filter framework=jax model=cont_a name=f ! "
            "tensor_sink name=sink")

    def test_stop_writes_state_and_restore_rearms(self, swap_models,
                                                  tmp_path):
        ckpt = str(tmp_path / "ckpt")
        pipe = parse_launch(self.DESC)
        pipe.checkpoint_dir = ckpt
        msg = pipe.run(timeout=60)
        assert msg is not None and msg.kind == "eos", msg
        sched_state = pipe._slo_scheduler.checkpoint_state()
        path = os.path.join(ckpt, continuity.STATE_FILE)
        assert os.path.isfile(path), "stop() did not checkpoint"

        pipe2 = parse_launch(self.DESC)
        pipe2.checkpoint_dir = ckpt
        pipe2.start()  # maybe_restore_env picks up the state file
        try:
            assert pipe2._continuity_restored
            got = pipe2._slo_scheduler.checkpoint_state()
            assert got["estimator"] == sched_state["estimator"], \
                "service-rate EWMAs did not survive the restart"
        finally:
            pipe2.stop()

    def test_explicit_checkpoint_restore_api(self, swap_models, tmp_path):
        pipe = parse_launch(self.DESC)
        pipe.start()
        try:
            path = pipe.checkpoint(str(tmp_path))
            assert os.path.isfile(path)
            applied = pipe.restore(str(tmp_path))
            assert applied["pipeline"] == pipe.name
        finally:
            pipe.stop()

    def test_version_mismatch_refuses(self, swap_models, tmp_path):
        pipe = parse_launch(self.DESC)
        pipe.start()
        try:
            pipe.checkpoint(str(tmp_path))
        finally:
            pipe.stop()
        import pickle

        path = os.path.join(str(tmp_path), continuity.STATE_FILE)
        with open(path, "rb") as f:
            state = pickle.load(f)
        state["version"] = 999
        with open(path, "wb") as f:
            pickle.dump(state, f)
        pipe2 = parse_launch(self.DESC)
        with pytest.raises(ValueError, match="state version"):
            pipe2.restore(str(tmp_path))

    def test_corrupt_checkpoint_never_fails_teardown(self, swap_models,
                                                     tmp_path,
                                                     monkeypatch):
        # an unwritable checkpoint dir must log, not raise, on stop()
        target = tmp_path / "blocked"
        target.write_text("a file where a directory must go")
        pipe = parse_launch(self.DESC)
        pipe.checkpoint_dir = str(target)
        msg = pipe.run(timeout=60)  # stop() runs inside run()
        assert msg is not None and msg.kind == "eos", msg


# -- kill switches ------------------------------------------------------------


class TestKillSwitches:
    def test_unset_env_writes_nothing(self, swap_models, tmp_path,
                                      monkeypatch):
        import jax

        monkeypatch.delenv(continuity.CHECKPOINT_ENV, raising=False)
        # a process started without the variable: neither the
        # environment nor jax.config names a cache directory
        monkeypatch.delenv(continuity.JAX_CACHE_ENV, raising=False)
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.chdir(tmp_path)
        pipe = parse_launch(
            "videotestsrc num-buffers=4 ! tensor_converter ! "
            "tensor_filter framework=jax model=cont_a ! fakesink")
        msg = pipe.run(timeout=60)
        assert msg is not None and msg.kind == "eos", msg
        assert pipe.checkpoint_dir is None
        assert not pipe._continuity_restored
        assert continuity.compile_cache_dir() is None, \
            "Pipeline.start() armed the cache with nothing asking for it"
        assert list(tmp_path.iterdir()) == [], \
            "unarmed continuity wrote files"

    def test_maybe_restore_without_state_file_is_noop(self, swap_models,
                                                      tmp_path):
        pipe = parse_launch(
            "videotestsrc num-buffers=1 ! tensor_converter ! fakesink")
        pipe.checkpoint_dir = str(tmp_path)  # armed, but no state file
        assert continuity.maybe_restore_env(pipe) is None
        assert not pipe._continuity_restored

    def test_env_arms_checkpoint_on_stop(self, swap_models, tmp_path,
                                         monkeypatch, cache_env):
        monkeypatch.setenv(continuity.CHECKPOINT_ENV, str(tmp_path))
        pipe = parse_launch(
            "videotestsrc num-buffers=2 ! tensor_converter ! "
            "tensor_filter framework=jax model=cont_a ! fakesink")
        msg = pipe.run(timeout=60)
        assert msg is not None and msg.kind == "eos", msg
        assert os.path.isfile(
            os.path.join(str(tmp_path), continuity.STATE_FILE))
        # the armed checkpoint dir armed the compile cache too — where
        # the one rule says, not in a subdirectory of the checkpoint
        assert continuity.compile_cache_dir() == cache_env
        assert not os.path.exists(os.path.join(str(tmp_path), "xla-cache"))


# -- persistent compile cache -------------------------------------------------


class TestCompileCache:
    def test_variable_set_means_that_directory_and_no_override(
            self, cache_env, monkeypatch):
        """JAX_COMPILATION_CACHE_DIR set: JAX's own handling stands — the
        program never writes ``jax_compilation_cache_dir`` itself."""
        import jax

        assert continuity.resolve_compile_cache_dir() == cache_env
        writes = []
        real_update = jax.config.update
        monkeypatch.setattr(
            jax.config, "update",
            lambda name, val: (writes.append(name), real_update(name, val)))
        assert continuity.arm_compile_cache() == cache_env
        assert "jax_compilation_cache_dir" not in writes
        assert continuity.compile_cache_dir() == cache_env

    def test_variable_unset_means_checkout_jax_cache(self, tmp_path,
                                                     monkeypatch):
        """Unset: ``<checkout>/.jax_cache`` — a fixed path, because the
        path is part of the cache key and a directory that moves never
        hits. (Armed against a stand-in so the test stays hermetic.)"""
        import jax

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert continuity.DEFAULT_CACHE_DIR == os.path.join(repo,
                                                            ".jax_cache")
        monkeypatch.delenv(continuity.JAX_CACHE_ENV)
        assert continuity.resolve_compile_cache_dir() == \
            continuity.DEFAULT_CACHE_DIR
        stand_in = str(tmp_path / "checkout" / ".jax_cache")
        monkeypatch.setattr(continuity, "DEFAULT_CACHE_DIR", stand_in)
        assert continuity.arm_compile_cache() == stand_in
        assert jax.config.jax_compilation_cache_dir == stand_in
        assert os.path.isdir(stand_in)

    def test_variable_set_after_jax_import_is_an_error_not_an_inert_cache(
            self, tmp_path, monkeypatch):
        """jax.config took the variable at import; one set later names a
        directory JAX never writes. Reporting it as armed would print a
        cache directory in the smoke/bench JSON that is not in use."""
        late = str(tmp_path / "set-too-late")
        monkeypatch.setenv(continuity.JAX_CACHE_ENV, late)
        with pytest.raises(RuntimeError,
                           match="JAX_COMPILATION_CACHE_DIR.*after"):
            continuity.arm_compile_cache()
        assert continuity.compile_cache_dir() is None
        # Pipeline.start()'s hook serves cold instead of failing the start
        assert continuity.maybe_arm_compile_cache() is None
        assert continuity.compile_cache_dir() is None

    def test_uncreatable_default_names_the_variable(self, tmp_path,
                                                    monkeypatch):
        blocker = tmp_path / "a-file"
        blocker.write_text("")
        monkeypatch.delenv(continuity.JAX_CACHE_ENV)
        monkeypatch.setattr(continuity, "DEFAULT_CACHE_DIR",
                            str(blocker / ".jax_cache"))
        with pytest.raises(OSError, match="JAX_COMPILATION_CACHE_DIR"):
            continuity.arm_compile_cache()

    def test_cli_rejects_the_old_compile_cache_dir_spelling(
            self, tmp_path, capsys):
        """``--compile-cache`` took a DIR before PR 21; the old spelling
        must not parse the directory as the pipeline description."""
        from nnstreamer_tpu import cli

        with pytest.raises(SystemExit) as exc:
            cli.main(["--compile-cache", str(tmp_path),
                      "videotestsrc num-buffers=1 ! tensor_sink"])
        assert exc.value.code == 2
        err = capsys.readouterr().err
        assert "takes no directory" in err and \
            "JAX_COMPILATION_CACHE_DIR" in err

    def test_fresh_process_writes_where_the_variable_says(self, tmp_path):
        """The real thing, no fixture sleight of hand: a new interpreter
        with the variable set compiles one program and the entry lands
        in that directory."""
        import subprocess
        import sys

        target = tmp_path / "from-env"
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        prog = (
            "import jax\n"
            "from nnstreamer_tpu.pipeline import continuity\n"
            "print(continuity.arm_compile_cache())\n"
            "jax.jit(lambda x: x * 2.125 + 7.375)(1.0).block_until_ready()\n"
            "print(continuity.cache_stats()['misses'])\n")
        proc = subprocess.run(
            [sys.executable, "-c", prog], cwd=repo, capture_output=True,
            text=True, timeout=120,
            env=dict(os.environ, JAX_PLATFORMS="cpu",
                     JAX_COMPILATION_CACHE_DIR=str(target)))
        assert proc.returncode == 0, proc.stderr
        reported, misses = proc.stdout.split()
        assert reported == str(target) and int(misses) >= 1
        assert any(target.iterdir()), "no cache entry was written there"
        assert not os.path.exists(os.path.join(repo, ".jax_cache",
                                               target.name))

    def test_cleared_jit_cache_reloads_from_disk(self, cache_env):
        import jax

        continuity.arm_compile_cache()
        # idempotent re-arm is a no-op
        assert continuity.arm_compile_cache() == cache_env

        # odd constants: a program no other test in this process has
        # compiled yet, so the cold trace is a genuine cache miss
        fn = jax.jit(lambda x: x * 2.125 + 7.375)
        x = jnp.arange(8, dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(fn(x)),
                                   np.arange(8) * 2.125 + 7.375)
        before = continuity.cache_stats()
        assert before["misses"] >= 1, "cold compile never hit the cache"

        jax.clear_caches()  # simulate the restarted process
        fn2 = jax.jit(lambda x: x * 2.125 + 7.375)
        np.testing.assert_allclose(np.asarray(fn2(x)),
                                   np.arange(8) * 2.125 + 7.375)
        after = continuity.cache_stats()
        assert after["hits"] > before["hits"], \
            "warm trace compiled instead of loading from the cache"

    def test_materialized_host_buffers_own_their_bytes(self):
        # warm-boot regression: a cache-deserialized fused program keeps
        # its input-output aliasing, so outputs live in donated slabs; a
        # zero-copy to_host view of one would dangle after the dispatch
        # fence. Materialization must detach from the XLA buffer.
        buf = TensorBuffer([jnp.arange(8, dtype=jnp.float32)])
        host = buf.to_host()
        v = host.tensors[0]
        assert isinstance(v, np.ndarray)
        assert v.base is None and v.flags.owndata, \
            "to_host returned a view into an XLA buffer"

    def test_manifest_written_with_region_signatures(self, swap_models,
                                                     tmp_path):
        import json

        continuity.arm_compile_cache()
        pipe = parse_launch(
            "videotestsrc num-buffers=2 ! tensor_converter ! "
            "tensor_transform mode=arithmetic option=typecast:float32 ! "
            "tensor_filter framework=jax model=cont_a name=f ! "
            "tensor_sink name=sink")
        msg = pipe.run(timeout=60)
        assert msg is not None and msg.kind == "eos", msg
        path = continuity.write_program_manifest(pipe)
        assert path is not None
        doc = json.loads(open(path).read())
        assert doc["programs"], "no fused-region signatures recorded"
        sig = doc["programs"][0]
        assert sig["signature"] and len(sig["signature"]) == 16
        assert any(m["model"] == "cont_a" for m in sig["members"])
