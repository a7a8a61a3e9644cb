"""Aux subsystems: checkpoint/resume, native core, config system
(SURVEY §5 parity tests)."""

import os

import numpy as np
import pytest

from nnstreamer_tpu import parse_launch


class TestCheckpoint:
    def test_params_roundtrip(self, tmp_path):
        from nnstreamer_tpu.utils.checkpoint import load_params, save_params
        from nnstreamer_tpu.models.transformer import (
            TransformerConfig,
            init_params,
        )
        import jax.numpy as jnp

        cfg = TransformerConfig(vocab=32, d_model=16, n_heads=2, n_layers=1,
                                d_ff=32, dtype=jnp.float32)
        params = init_params(cfg)
        path = tmp_path / "m.msgpack"
        save_params(params, str(path))
        loaded = load_params(init_params(cfg, seed=1), str(path))
        np.testing.assert_array_equal(np.asarray(loaded["embed"]),
                                      np.asarray(params["embed"]))

    def test_stream_state_resume(self, tmp_path):
        """LSTM-style repo state survives a save/restore cycle (reference
        pattern: tensor_repo slots persist loop state)."""
        from nnstreamer_tpu.elements.repo import GLOBAL_REPO
        from nnstreamer_tpu.tensors.buffer import TensorBuffer
        from nnstreamer_tpu.utils.checkpoint import (
            restore_stream_state,
            save_stream_state,
        )

        GLOBAL_REPO.set("h0", TensorBuffer([np.arange(4, dtype=np.float32)]))
        path = str(tmp_path / "stream.ckpt")
        save_stream_state(path, extra={"step": 42})
        GLOBAL_REPO.remove("h0")
        assert GLOBAL_REPO.peek("h0") is None
        extra = restore_stream_state(path)
        assert extra["step"] == 42
        np.testing.assert_array_equal(GLOBAL_REPO.peek("h0")[0],
                                      np.arange(4, dtype=np.float32))

    def test_msgpack_model_via_filter(self, tmp_path):
        """Save transformer params, load via framework=jax model=.msgpack
        custom=module:<factory> (the reference's model-file pattern)."""
        import jax.numpy as jnp

        from nnstreamer_tpu.models import transformer_lm
        from nnstreamer_tpu.single import SingleShot
        from nnstreamer_tpu.utils.checkpoint import save_params

        fn, params, _, _ = transformer_lm(vocab=32, d_model=16, n_heads=2,
                                          n_layers=1, d_ff=32, seq=8,
                                          dtype=jnp.float32)
        path = tmp_path / "lm.msgpack"
        save_params(params, str(path))
        s = SingleShot(framework="jax", model=str(path),
                       custom="module:transformer_lm")
        out = s.invoke([np.zeros((1, 8), np.int32)])
        # output vocab follows the LOADED params (32), not the factory
        # template default — the checkpoint's shapes win
        assert np.asarray(out[0]).shape == (1, 8, 32)
        s.close()


class TestNative:
    def test_library_loads(self):
        from nnstreamer_tpu import native

        assert native.available()
        feats = native.cpu_features()
        assert feats["native"]

    def test_sparse_native_matches_numpy(self, rng):
        from nnstreamer_tpu import native

        for dtype in (np.float32, np.uint8, np.int64, np.float16):
            d = (rng.random(512) < 0.05).astype(dtype)
            idx, vals = native.sparse_encode_arrays(d)
            np.testing.assert_array_equal(idx, np.flatnonzero(d))
            back = native.sparse_decode_arrays(idx, vals, d.size)
            np.testing.assert_array_equal(back, d)

    def test_sparse_decode_rejects_bad_index(self):
        from nnstreamer_tpu import native

        with pytest.raises(ValueError):
            native.sparse_decode_arrays(
                np.array([999], np.uint32), np.array([1.0], np.float32), 10
            )


class TestConfig:
    def test_env_override(self, monkeypatch):
        from nnstreamer_tpu.config import Conf

        monkeypatch.setenv("NNSTREAMER_TPU_FILTER_FRAMEWORK_PRIORITY_XYZ",
                           "torch,jax")
        conf = Conf()
        assert conf.framework_priority("model.xyz") == ["torch", "jax"]

    def test_ini_file(self, tmp_path, monkeypatch):
        ini = tmp_path / "conf.ini"
        ini.write_text("[jax]\nplatform = cpu\n[filter]\npath = /opt/plugins\n")
        monkeypatch.setenv("NNSTREAMER_TPU_CONF", str(ini))
        from nnstreamer_tpu.config import Conf

        conf = Conf()
        assert conf.get("jax", "platform") == "cpu"
        assert conf.subplugin_paths("filter") == ["/opt/plugins"]

    def test_default_ext_priority(self):
        from nnstreamer_tpu.config import Conf

        assert "jax" in Conf().framework_priority("model.msgpack")
        assert "torch" in Conf().framework_priority("model.pt")


class TestNoCpuFallback:
    """A process that wants the chip fails without it: nothing probes in
    a child process, caches a verdict or switches to the CPU on its own
    (PR 21). bench.py and chip_smoke.py refuse a CPU backend outright."""

    REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    @pytest.fixture
    def repo_on_path(self, monkeypatch):
        monkeypatch.syspath_prepend(self.REPO)

    def test_bench_entry_refuses_cpu(self, repo_on_path):
        import bench

        with pytest.raises(SystemExit) as e:
            bench._require_tpu()
        assert "backend 'cpu'" in str(e.value)

    def test_bench_process_exits_nonzero_without_a_result(self):
        import subprocess
        import sys

        proc = subprocess.run(
            [sys.executable, "bench.py"], cwd=self.REPO,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=120)
        assert proc.returncode != 0
        assert "needs a TPU" in proc.stderr, proc.stderr
        assert proc.stdout.strip() == "", "a refused bench printed a result"

    def test_unknown_device_kind_is_an_error_not_none(self, repo_on_path):
        import bench

        with pytest.raises(RuntimeError, match="peaks table"):
            bench._peak_flops()  # device_kind "cpu" has no bf16 peak

    def test_smoke_entry_refuses_cpu_and_unknown_chips(self, repo_on_path,
                                                       monkeypatch):
        import jax

        import chip_smoke

        with pytest.raises(SystemExit) as e:
            chip_smoke.require_tpu()
        assert "backend 'cpu'" in str(e.value)

        class FakeChip:
            device_kind = "TPU v99"

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(jax, "devices", lambda *a: [FakeChip()])
        with pytest.raises(SystemExit) as e:
            chip_smoke.require_tpu()
        assert "TPU v99" in str(e.value)

    def test_the_fallback_helper_is_gone(self):
        import glob
        import importlib.util

        assert importlib.util.find_spec(
            "nnstreamer_tpu.utils.platform") is None
        helper = "ensure_jax_" + "platform"  # (kept out of repo greps)
        for path in glob.glob(os.path.join(self.REPO, "examples", "*.py")):
            with open(path) as f:
                assert helper not in f.read(), path


class TestEndToEndLatency:
    """North-star latency stat: source create() stamps, sink measures at
    materialization (BASELINE.md; reference tensor_filter.c:349-423)."""

    def test_latency_recorded_per_frame(self):
        from nnstreamer_tpu import parse_launch

        pipe = parse_launch(
            "videotestsrc num-buffers=6 width=8 height=8 ! "
            "tensor_converter ! tensor_sink name=out")
        msg = pipe.run(timeout=30)
        assert msg is not None and msg.kind == "eos"
        sink = pipe.get("out")
        assert len(sink.latencies) == 6
        p50, p99 = sink.latency_percentiles(50, 99)
        assert 0 < p50 <= p99 < 10_000

    def test_microbatched_latency_counts_batch_wait(self):
        """Aggregated buffers carry one stamp per constituent frame, so
        latency includes the batch-window wait and the count equals the
        FRAME count, not the buffer count."""
        from nnstreamer_tpu import parse_launch

        pipe = parse_launch(
            "videotestsrc num-buffers=8 width=8 height=8 ! "
            "tensor_converter ! "
            "tensor_aggregator frames-in=1 frames-out=4 frames-flush=4 "
            "frames-dim=3 concat=true ! tensor_sink name=out")
        msg = pipe.run(timeout=30)
        assert msg is not None and msg.kind == "eos"
        sink = pipe.get("out")
        assert len(sink.buffers) == 2
        assert len(sink.latencies) == 8  # per frame, not per buffer
        assert sink.latency_percentiles() is not None

    def test_mixed_stamped_unstamped_frames_stay_aligned(self):
        """Frames pushed without create stamps interleaved with stamped
        ones must not shift stamp→frame attribution: the aggregator pads
        placeholders so each emitted window reports only its own frames'
        stamps (ADVICE r4: aggregator.py stamp/window lockstep)."""
        import time

        from nnstreamer_tpu.elements.aggregator import TensorAggregator
        from nnstreamer_tpu.elements.sink import TensorSink
        from nnstreamer_tpu.tensors.buffer import TensorBuffer

        agg = TensorAggregator("agg")
        agg.set_property("frames_in", 1)
        agg.set_property("frames_out", 2)
        agg.set_property("frames_flush", 2)
        agg.set_property("frames_dim", 0)
        agg.set_property("concat", True)
        sink = TensorSink("out")
        agg.srcpad.link(sink.sinkpad)
        arr = np.zeros((1, 4), np.float32)
        t0 = time.time() - 5.0  # distinctively old stamp
        # window 1: unstamped + stamped(t0); window 2: stamped(now) x2
        agg.chain(agg.sinkpad, TensorBuffer([arr], pts=0))
        agg.chain(agg.sinkpad,
                  TensorBuffer([arr], pts=1, meta={"create_t": t0}))
        now = time.time()
        agg.chain(agg.sinkpad,
                  TensorBuffer([arr], pts=2, meta={"create_t": now}))
        agg.chain(agg.sinkpad,
                  TensorBuffer([arr], pts=3, meta={"create_t": now}))
        assert len(sink.buffers) == 2
        w1 = sink.buffers[0].meta.get("create_ts")
        w2 = sink.buffers[1].meta.get("create_ts")
        assert w1 == [t0]          # placeholder filtered, stamp not shifted
        assert w2 == [now, now]    # second window owns only its stamps

    def test_mux_latency_spans_all_streams(self):
        from nnstreamer_tpu import parse_launch

        pipe = parse_launch(
            "tensor_mux name=m sync-mode=slowest ! tensor_sink name=out "
            "videotestsrc num-buffers=3 width=4 height=4 ! "
            "tensor_converter ! m. "
            "videotestsrc num-buffers=3 width=4 height=4 ! "
            "tensor_converter ! m.")
        msg = pipe.run(timeout=30)
        assert msg is not None and msg.kind == "eos"
        sink = pipe.get("out")
        assert len(sink.latencies) == 6  # 3 muxed frames x 2 streams
