"""Latency-budget adaptive batching — ``tensor_aggregator
latency-budget-ms`` (round-5 VERDICT #1).

A micro-batched stream trades per-frame latency for throughput: with
batch=8 at 30 fps, a frame's p50 latency IS the batch window (~264 ms
measured on the flagship stream). Budget mode bounds the admission wait: a window
holding frames past the budget flushes early, padded to the compiled
batch shape (meta["valid_frames"]), and the sink trims the padding.
The reference's per-frame path (tensor_filter.c:349-423) has no window
wait at all — this is the TPU-batched design matching its latency
semantics without giving up the batched MXU dispatch.
"""

import time

import numpy as np
import pytest

from nnstreamer_tpu.elements.aggregator import TensorAggregator
from nnstreamer_tpu.elements.sink import TensorSink
from nnstreamer_tpu.tensors.buffer import TensorBuffer


def _wire(budget_ms, fout=4, fd=1):
    agg = TensorAggregator("agg")
    agg.set_property("frames_in", 1)
    agg.set_property("frames_out", fout)
    agg.set_property("frames_flush", fout)
    agg.set_property("frames_dim", fd)  # unit [1,4] → concat axis 0
    agg.set_property("concat", True)
    agg.set_property("latency_budget_ms", budget_ms)
    sink = TensorSink("out")
    agg.srcpad.link(sink.sinkpad)
    return agg, sink


def _frame(i):
    return np.full((1, 4), float(i), np.float32)


class TestPartialFlush:
    def test_watchdog_flushes_stalled_window(self):
        """Frames short of a full window flush within ~budget once the
        upstream stalls — the flusher thread, not an arrival, triggers."""
        agg, sink = _wire(budget_ms=30)
        agg.start()
        try:
            t0 = time.monotonic()
            agg.chain(agg.sinkpad, TensorBuffer(
                [_frame(0)], pts=0, meta={"create_t": t0}))
            agg.chain(agg.sinkpad, TensorBuffer(
                [_frame(1)], pts=1, meta={"create_t": t0}))
            deadline = time.monotonic() + 2.0
            while not sink.buffers and time.monotonic() < deadline:
                time.sleep(0.005)
            assert len(sink.buffers) == 1
            waited = time.monotonic() - t0
            assert waited < 0.5  # flushed by budget, not by this test's poll
            out = sink.buffers[0]
            # sink trimmed the repeat-last padding to the 2 valid frames
            assert out.tensors[0].shape == (2, 4)
            np.testing.assert_array_equal(
                out.tensors[0], np.vstack([_frame(0), _frame(1)]))
            assert out.meta["valid_frames"] == 2
            assert len(out.meta["create_ts"]) == 2
            # only the real frames got latency stamps
            assert len(sink.latencies) == 2
        finally:
            agg.stop()

    def test_unstamped_frames_use_arrival_clock(self):
        agg, sink = _wire(budget_ms=25)
        agg.start()
        try:
            agg.chain(agg.sinkpad, TensorBuffer([_frame(7)], pts=0))
            deadline = time.monotonic() + 2.0
            while not sink.buffers and time.monotonic() < deadline:
                time.sleep(0.005)
            assert len(sink.buffers) == 1
            assert sink.buffers[0].meta["valid_frames"] == 1
            assert sink.buffers[0].tensors[0].shape == (1, 4)
        finally:
            agg.stop()

    def test_saturated_stream_never_pads(self):
        """Back-to-back arrivals fill windows faster than any budget: the
        throughput path emits only full, unpadded windows."""
        agg, sink = _wire(budget_ms=50)
        agg.start()
        try:
            for i in range(8):
                agg.chain(agg.sinkpad, TensorBuffer([_frame(i)], pts=i))
            assert len(sink.buffers) == 2
            for out in sink.buffers:
                assert "valid_frames" not in out.meta
                assert out.tensors[0].shape == (4, 4)
            got = np.vstack([b.tensors[0] for b in sink.buffers])
            np.testing.assert_array_equal(
                got, np.vstack([_frame(i) for i in range(8)]))
        finally:
            agg.stop()

    def test_eos_flushes_partial_tail(self):
        """Budget mode promises every frame a bounded exit: the tail
        short of a window flushes at EOS instead of being dropped."""
        from nnstreamer_tpu.pipeline.element import EosEvent

        agg, sink = _wire(budget_ms=10_000)  # budget never fires
        for i in range(3):
            agg.chain(agg.sinkpad, TensorBuffer([_frame(i)], pts=i))
        assert not sink.buffers
        agg.sinkpad.eos = True
        agg.sink_event(agg.sinkpad, EosEvent())
        assert len(sink.buffers) == 1
        assert sink.buffers[0].meta["valid_frames"] == 3
        assert sink.buffers[0].tensors[0].shape == (3, 4)
        assert sink.eos

    def test_concat_false_partial_emits_unpadded(self):
        """concat=false has no single padded tensor to trim: the budget
        flush emits the k real unit tensors, no padding, no
        valid_frames meta."""
        from nnstreamer_tpu.pipeline.element import EosEvent

        agg, sink = _wire(budget_ms=10_000)
        agg.set_property("concat", False)
        for i in range(2):
            agg.chain(agg.sinkpad, TensorBuffer([_frame(i)], pts=i))
        agg.sinkpad.eos = True
        agg.sink_event(agg.sinkpad, EosEvent())
        assert len(sink.buffers) == 1
        out = sink.buffers[0]
        assert "valid_frames" not in out.meta
        assert len(out.tensors) == 2  # the 2 real frames, nothing extra
        np.testing.assert_array_equal(out.tensors[0], _frame(0))
        np.testing.assert_array_equal(out.tensors[1], _frame(1))

    def test_non_leading_axis_partial_emits_unpadded(self):
        """frames_dim that concatenates along a NON-leading axis (e.g.
        audio windows) cannot use the sink's axis-0 trim: the budget
        flush emits the shorter window unpadded, every sample real."""
        from nnstreamer_tpu.pipeline.element import EosEvent

        agg, sink = _wire(budget_ms=10_000, fd=0)  # [1,4] → concat axis 1
        for i in range(2):
            agg.chain(agg.sinkpad, TensorBuffer([_frame(i)], pts=i))
        agg.sinkpad.eos = True
        agg.sink_event(agg.sinkpad, EosEvent())
        assert len(sink.buffers) == 1
        out = sink.buffers[0]
        assert "valid_frames" not in out.meta
        assert out.tensors[0].shape == (1, 8)  # 2 windows of 4, no pad
        np.testing.assert_array_equal(
            out.tensors[0], np.hstack([_frame(0), _frame(1)]))

    def test_budget_off_keeps_reference_semantics(self):
        """Without a budget the partial tail stays queued (reference
        tensor_aggregator drops incomplete windows at EOS)."""
        from nnstreamer_tpu.pipeline.element import EosEvent

        agg, sink = _wire(budget_ms=0)
        for i in range(3):
            agg.chain(agg.sinkpad, TensorBuffer([_frame(i)], pts=i))
        agg.sinkpad.eos = True
        agg.sink_event(agg.sinkpad, EosEvent())
        assert not sink.buffers


class TestPipelineExactness:
    """Partial-vs-full-batch results are token-exact through a real
    jitted filter: padding rows never change the valid rows' outputs."""

    @pytest.fixture
    def rowsum_model(self):
        import jax.numpy as jnp

        from nnstreamer_tpu.filters.jax_backend import (
            register_jax_model,
            unregister_jax_model,
        )

        def fn(p, x):  # [4, 8] → per-row checksum [4]
            return (jnp.sum(x * p, axis=1),)

        register_jax_model(
            "lat_budget_rowsum", fn,
            np.arange(8, dtype=np.float32) + 1.0)
        yield "lat_budget_rowsum"
        unregister_jax_model("lat_budget_rowsum")

    def _run(self, rowsum_model, frames, paced_ms):
        from nnstreamer_tpu import parse_launch

        pipe = parse_launch(
            "appsrc name=src ! "
            "tensor_aggregator frames-in=1 frames-out=4 frames-flush=4 "
            "frames-dim=1 concat=true latency-budget-ms=25 ! "
            f"tensor_filter framework=jax model={rowsum_model} ! "
            "tensor_sink name=sink")
        src, sink = pipe.get("src"), pipe.get("sink")
        pipe.start()
        try:
            for f in frames:
                src.push([f])
                if paced_ms:
                    time.sleep(paced_ms / 1e3)
            src.end_of_stream()
            msg = pipe.wait(timeout=60)
            assert msg is not None and msg.kind == "eos", msg
            return [np.asarray(b.tensors[0]) for b in sink.buffers]
        finally:
            pipe.stop()

    def test_paced_partial_equals_full_batch_math(self, rowsum_model):
        rng = np.random.default_rng(0)
        frames = [rng.standard_normal((1, 8)).astype(np.float32)
                  for _ in range(6)]
        # paced slower than the budget → partial (padded) dispatches
        outs = self._run(rowsum_model, frames, paced_ms=45)
        got = np.concatenate([o.reshape(-1) for o in outs])
        assert got.shape == (6,)  # every frame exited, no padding leaked
        want = np.concatenate(
            [f @ (np.arange(8, dtype=np.float32) + 1.0) for f in frames])
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
        # and at least one dispatch really was partial
        assert len(outs) > 2

    def test_pad_device_partial_equals_host_pad(self, rowsum_model):
        """pad-device defers the zero-pad to the staging queue's
        prefetch: only k real frames cross H2D, the filter still sees
        the full-window shape, and results match the host-pad path."""
        from nnstreamer_tpu import parse_launch

        rng = np.random.default_rng(2)
        frames = [rng.standard_normal((1, 8)).astype(np.float32)
                  for _ in range(6)]
        pipe = parse_launch(
            "appsrc name=src ! "
            "tensor_aggregator frames-in=1 frames-out=4 frames-flush=4 "
            "frames-dim=1 concat=true latency-budget-ms=25 "
            "pad-device=true ! "
            "queue max-size-buffers=4 prefetch-device=true ! "
            f"tensor_filter framework=jax model={rowsum_model} ! "
            "tensor_sink name=sink")
        src, sink = pipe.get("src"), pipe.get("sink")
        pipe.start()
        try:
            # first window full (announces caps), then paced partials
            for f in frames[:4]:
                src.push([f])
            time.sleep(0.2)
            for f in frames[4:]:
                src.push([f])
                time.sleep(0.045)
            src.end_of_stream()
            msg = pipe.wait(timeout=60)
            assert msg is not None and msg.kind == "eos", msg
        finally:
            pipe.stop()
        got = np.concatenate(
            [np.asarray(b.tensors[0]).reshape(-1) for b in sink.buffers])
        assert got.shape == (6,)
        want = np.concatenate(
            [f @ (np.arange(8, dtype=np.float32) + 1.0) for f in frames])
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
        # the partial really deferred its pad (k frames < window)
        assert any(b.meta.get("valid_frames") for b in sink.buffers)

    def test_burst_full_batches_unaffected(self, rowsum_model):
        rng = np.random.default_rng(1)
        frames = [rng.standard_normal((1, 8)).astype(np.float32)
                  for _ in range(8)]
        outs = self._run(rowsum_model, frames, paced_ms=0)
        got = np.concatenate([o.reshape(-1) for o in outs])
        want = np.concatenate(
            [f @ (np.arange(8, dtype=np.float32) + 1.0) for f in frames])
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
