"""Tests for the obs subsystem: registry primitives, Prometheus/JSON
exporters, queue-drop accounting, and property/exporter agreement.

Pipelines here use unique names — registry metric identity is
(name, labels) process-wide, so a shared pipeline/element name would
accumulate counts across tests.
"""

import json
import logging
import threading
import urllib.request

import pytest

from nnstreamer_tpu.obs import (
    LATENCY_BUCKETS_S,
    MetricsRegistry,
    MetricsServer,
    get_registry,
)
from nnstreamer_tpu.pipeline.element import Element, EosEvent, FlowReturn
from nnstreamer_tpu.pipeline.pipeline import Pipeline, Queue, SourceElement
from nnstreamer_tpu.tensors.buffer import TensorBuffer
from nnstreamer_tpu.utils.stats import InvokeStats

import numpy as np


class TestCounter:
    def test_inc_and_value(self):
        reg = MetricsRegistry()
        c = reg.counter("t_total", "help", a="1")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5

    def test_negative_inc_rejected(self):
        c = MetricsRegistry().counter("t_total")
        with pytest.raises(ValueError, match="only go up"):
            c.inc(-1)

    def test_set_total_monotonic(self):
        c = MetricsRegistry().counter("t_total")
        c.set_total(10)
        c.set_total(4)  # stale external read must not regress the counter
        assert c.value == 10


class TestGauge:
    def test_set_inc_dec(self):
        g = MetricsRegistry().gauge("t_g")
        g.set(5)
        g.inc(2)
        g.dec()
        assert g.value == 6

    def test_callback_gauge(self):
        reg = MetricsRegistry()
        g = reg.gauge("t_g", fn=lambda: 42.0)
        assert g.value == 42.0

    def test_broken_callback_reads_zero(self):
        g = MetricsRegistry().gauge("t_g", fn=lambda: 1 / 0)
        assert g.value == 0.0


class TestHistogram:
    def test_cumulative_buckets(self):
        h = MetricsRegistry().histogram("t_h", buckets=(1.0, 2.0, 4.0))
        for v in (0.5, 1.5, 1.5, 3.0, 100.0):
            h.observe(v)
        assert h.bucket_counts() == [
            (1.0, 1), (2.0, 3), (4.0, 4), (float("inf"), 5)]
        assert h.count == 5
        assert h.sum == pytest.approx(106.5)

    def test_boundary_value_lands_in_its_bucket(self):
        # le semantics: an observation equal to a bound counts under it
        h = MetricsRegistry().histogram("t_h", buckets=(1.0, 2.0))
        h.observe(1.0)
        assert h.bucket_counts()[0] == (1.0, 1)

    def test_percentile_interpolates(self):
        h = MetricsRegistry().histogram("t_h", buckets=(1.0, 2.0, 4.0))
        for _ in range(100):
            h.observe(1.5)  # all mass in the (1, 2] bucket
        # rank interpolates linearly inside the winning bucket
        assert h.percentile(50) == pytest.approx(1.5)
        assert h.percentile(100) == pytest.approx(2.0)

    def test_percentile_empty_is_none(self):
        assert MetricsRegistry().histogram("t_h").percentile(99) is None

    def test_percentile_inf_tail_is_last_bound(self):
        h = MetricsRegistry().histogram("t_h", buckets=(1.0, 2.0))
        h.observe(50.0)
        assert h.percentile(99) == 2.0

    def test_default_buckets_span_latency_range(self):
        assert LATENCY_BUCKETS_S[0] == pytest.approx(100e-6)
        assert LATENCY_BUCKETS_S[-1] == 10.0


class TestRegistry:
    def test_get_or_create_identity(self):
        reg = MetricsRegistry()
        a = reg.counter("t_total", pipeline="p", element="e")
        b = reg.counter("t_total", element="e", pipeline="p")  # order-free
        assert a is b
        assert reg.counter("t_total", pipeline="p", element="x") is not a

    def test_kind_conflict_raises(self):
        reg = MetricsRegistry()
        reg.counter("t_metric", a="1")
        with pytest.raises(ValueError, match="already"):
            reg.gauge("t_metric", a="1")
        with pytest.raises(ValueError, match="already used"):
            reg.gauge("t_metric", a="2")  # same name, other labels

    def test_get_returns_none_when_absent(self):
        assert MetricsRegistry().get("nope", a="1") is None

    def test_collector_false_unregisters(self):
        reg = MetricsRegistry()
        calls = []
        reg.register_collector(lambda: calls.append(1) or False)
        reg.collect()
        reg.collect()
        assert len(calls) == 1

    def test_collector_exception_unregisters(self):
        reg = MetricsRegistry()
        reg.register_collector(lambda: 1 / 0)
        reg.collect()  # must not raise
        assert reg._collectors == []

    def test_render_prometheus_format(self):
        reg = MetricsRegistry()
        reg.counter("t_req_total", "requests", wire="nnstpu").inc(3)
        reg.histogram("t_lat_seconds", "latency",
                      buckets=(0.1, 1.0), pipeline="p").observe(0.05)
        text = reg.render_prometheus()
        assert "# HELP t_req_total requests" in text
        assert "# TYPE t_req_total counter" in text
        assert 't_req_total{wire="nnstpu"} 3' in text
        assert "# TYPE t_lat_seconds histogram" in text
        assert 't_lat_seconds_bucket{le="0.1",pipeline="p"} 1' in text
        assert 't_lat_seconds_bucket{le="+Inf",pipeline="p"} 1' in text
        assert 't_lat_seconds_sum{pipeline="p"} 0.05' in text
        assert 't_lat_seconds_count{pipeline="p"} 1' in text
        assert text.endswith("\n")

    def test_render_escapes_label_values(self):
        reg = MetricsRegistry()
        reg.counter("t_total", x='a"b\\c\nd').inc()
        line = [ln for ln in reg.render_prometheus().splitlines()
                if ln.startswith("t_total{")][0]
        assert line == 't_total{x="a\\"b\\\\c\\nd"} 1'

    def test_snapshot_is_json_able(self):
        reg = MetricsRegistry()
        reg.gauge("t_g", a="1").set(2)
        reg.histogram("t_h", buckets=(1.0,)).observe(0.5)
        snap = json.loads(json.dumps(reg.snapshot()))
        by_name = {m["name"]: m for m in snap["metrics"]}
        assert by_name["t_g"]["value"] == 2
        assert by_name["t_h"]["count"] == 1
        assert by_name["t_h"]["p50"] == pytest.approx(0.5)
        assert by_name["t_h"]["buckets"][-1][0] == "+Inf"


class TestMetricsServer:
    def test_http_exporter_end_to_end(self):
        reg = MetricsRegistry()
        reg.counter("t_req_total", "reqs", wire="x").inc(7)
        reg.histogram("t_lat_seconds", pipeline="p").observe(0.002)
        with MetricsServer(registry=reg, host="127.0.0.1", port=0) as srv:
            base = f"http://127.0.0.1:{srv.port}"
            with urllib.request.urlopen(f"{base}/metrics") as resp:
                assert resp.headers["Content-Type"].startswith(
                    "text/plain; version=0.0.4")
                text = resp.read().decode()
            assert 't_req_total{wire="x"} 7' in text
            with urllib.request.urlopen(f"{base}/metrics.json") as resp:
                assert resp.headers["Content-Type"] == "application/json"
                snap = json.loads(resp.read())
            assert any(m["name"] == "t_lat_seconds"
                       for m in snap["metrics"])
            with urllib.request.urlopen(f"{base}/healthz") as resp:
                assert resp.read() == b"ok\n"
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(f"{base}/nope")

    def test_server_refreshes_collectors_per_scrape(self):
        reg = MetricsRegistry()
        state = {"v": 1.0}
        g = reg.gauge("t_g")

        def collect():
            g.set(state["v"])

        reg.register_collector(collect)
        with MetricsServer(registry=reg, host="127.0.0.1", port=0) as srv:
            base = f"http://127.0.0.1:{srv.port}"
            assert "t_g 1" in urllib.request.urlopen(
                f"{base}/metrics").read().decode()
            state["v"] = 2.0
            assert "t_g 2" in urllib.request.urlopen(
                f"{base}/metrics").read().decode()


# -- pipeline-level instrumentation ------------------------------------------
class _NumSrc(SourceElement):
    ELEMENT_NAME = "_obsnumsrc"
    PROPERTIES = {**SourceElement.PROPERTIES, "num_buffers": 5}

    def __init__(self, name=None, **props):
        super().__init__(name, **props)
        self.i = 0

    def negotiate(self):
        from nnstreamer_tpu.tensors.types import TensorsConfig

        cfg = TensorsConfig.from_arrays([np.zeros((1,), np.float32)])
        self.srcpad.set_caps(cfg.to_caps())

    def create(self):
        if self.i >= self.get_property("num_buffers"):
            return None
        buf = TensorBuffer([np.array([float(self.i)], np.float32)],
                           pts=self.i * 1000)
        self.i += 1
        return buf


class _BlockingSink(Element):
    """Blocks its first chain() until released — pins the queue worker so
    queued buffers pile up deterministically."""

    ELEMENT_NAME = "_obsblocksink"

    def __init__(self, name=None, **props):
        super().__init__(name, **props)
        self.add_sink_pad("sink")
        self.entered = threading.Event()
        self.release = threading.Event()
        self.count = 0

    def chain(self, pad, buf):
        self.entered.set()
        self.release.wait(timeout=10)
        self.count += 1
        return FlowReturn.OK


class TestQueueDrops:
    def test_leaky_downstream_drops_counted(self):
        pipe = Pipeline(name="obs-qdrop", fuse=False)
        q = Queue(name="q", max_size_buffers=2, leaky="downstream")
        sink = _BlockingSink(name="bs")
        pipe.add_linked(q, sink)
        records = []
        handler = logging.Handler()
        handler.emit = records.append
        logging.getLogger("nnstreamer_tpu").addHandler(handler)
        q.start()
        try:
            mk = lambda i: TensorBuffer(  # noqa: E731
                [np.array([float(i)], np.float32)], pts=i)
            q.chain(q.sinkpads[0], mk(0))
            # worker now holds buf 0 inside the blocked sink: the queue
            # itself is empty with capacity 2
            assert sink.entered.wait(5)
            q.chain(q.sinkpads[0], mk(1))
            q.chain(q.sinkpads[0], mk(2))  # full
            for i in range(3, 6):          # each push drops the oldest
                q.chain(q.sinkpads[0], mk(i))
            drops = get_registry().get("nns_queue_drops_total",
                                       pipeline="obs-qdrop", element="q")
            assert drops is not None and drops.value == 3
            snap = q.obs_snapshot()
            assert snap["drops"] == 3
            assert snap["depth"] == 2
            # satellite: the drop is no longer silent — exactly one
            # rate-limited warning for the burst
            warns = [r for r in records
                     if r.levelno == logging.WARNING
                     and "leaky=downstream" in r.getMessage()]
            assert len(warns) == 1
        finally:
            sink.release.set()
            q.sink_event(q.sinkpads[0], EosEvent())
            q.stop()
            logging.getLogger("nnstreamer_tpu").removeHandler(handler)

    def test_depth_gauge_samples_live_queue(self):
        pipe = Pipeline(name="obs-qdepth", fuse=False)
        q = Queue(name="q", max_size_buffers=8)
        sink = _BlockingSink(name="bs")
        pipe.add_linked(q, sink)
        q.start()
        try:
            for i in range(4):
                q.chain(q.sinkpads[0], TensorBuffer(
                    [np.array([float(i)], np.float32)], pts=i))
            assert sink.entered.wait(5)
            depth = get_registry().get("nns_queue_depth",
                                       pipeline="obs-qdepth", element="q")
            assert depth is not None and depth.value == 3  # 1 in-flight
        finally:
            sink.release.set()
            q.sink_event(q.sinkpads[0], EosEvent())
            q.stop()


class TestPipelineMetrics:
    def test_metrics_snapshot_and_property_agreement(self):
        class _CountSink(Element):
            ELEMENT_NAME = "_obscountsink"

            def __init__(self, name=None, **props):
                super().__init__(name, **props)
                self.add_sink_pad("sink")
                self.count = 0

            def chain(self, pad, buf):
                self.count += 1
                return FlowReturn.OK

        src = _NumSrc(name="nsrc", num_buffers=6)
        sink = _CountSink(name="csink")
        pipe = Pipeline(name="obs-agree", fuse=False).add_linked(src, sink)
        assert pipe.run(timeout=10) is not None
        snap = pipe.metrics_snapshot()
        assert snap["pipeline"] == "obs-agree"
        s = snap["elements"]["csink"]
        assert s["invokes"] == 6
        assert s["latency_us"] == sink.get_property("latency")
        # the exporter's gauge is sampled from the same InvokeStats the
        # property reads, so the scraped value must agree exactly
        text = get_registry().render_prometheus()
        want = (f'nns_element_latency_us{{element="csink",'
                f'pipeline="obs-agree",type="_obscountsink"}} '
                f'{sink.get_property("latency")}')
        assert want in text
        assert (f'nns_element_invokes_total{{element="csink",'
                f'pipeline="obs-agree",type="_obscountsink"}} 6') in text

    def test_tensor_rate_drops_exported(self):
        from nnstreamer_tpu.elements.rate import TensorRate
        from nnstreamer_tpu.elements.sink import TensorSink

        src = _NumSrc(name="rsrc", num_buffers=10)
        rate = TensorRate(name="rate", framerate="30/1", throttle=False)
        sink = TensorSink(name="rsink")
        pipe = Pipeline(name="obs-rate", fuse=False)
        pipe.add_linked(src, rate, sink)
        assert pipe.run(timeout=10) is not None
        # pts step is 1µs, output period 1/30 s: the first frame emits,
        # the other nine land inside the same output period and drop
        assert rate.dropped == 9
        c = get_registry().get("nns_tensor_rate_dropped_total",
                               pipeline="obs-rate", element="rate")
        assert c is not None and c.value == rate.dropped
        assert pipe.metrics_snapshot()["elements"]["rate"]["drops"] == 9

    def test_sink_e2e_histogram_populated(self):
        from nnstreamer_tpu.elements.sink import TensorSink

        src = _NumSrc(name="esrc", num_buffers=5)
        sink = TensorSink(name="esink")
        pipe = Pipeline(name="obs-e2e", fuse=False).add_linked(src, sink)
        assert pipe.run(timeout=10) is not None
        h = get_registry().get("nns_sink_e2e_seconds",
                               pipeline="obs-e2e", element="esink")
        assert h is not None and h.count == len(sink.latencies) > 0
        snap = pipe.metrics_snapshot()["elements"]["esink"]
        assert "e2e_p50_ms" in snap and "e2e_p99_ms" in snap

    def test_mux_sync_wait_histogram(self):
        from nnstreamer_tpu.elements.mux import TensorMux

        src_a = _NumSrc(name="ma", num_buffers=4)
        src_b = _NumSrc(name="mb", num_buffers=4)
        mux = TensorMux(name="mux", sync_mode="nosync")
        from nnstreamer_tpu.elements.sink import TensorSink

        sink = TensorSink(name="msink")
        pipe = Pipeline(name="obs-mux", fuse=False)
        pipe.add(src_a, src_b, mux, sink)
        src_a.srcpad.link(mux.request_sink_pad())
        src_b.srcpad.link(mux.request_sink_pad())
        mux.srcpad.link(sink.sinkpads[0])
        assert pipe.run(timeout=10) is not None
        h = get_registry().get("nns_tensor_mux_sync_wait_seconds",
                               pipeline="obs-mux", element="mux")
        assert h is not None and h.count == 4


class TestInvokeStatsEdgeCases:
    def test_empty_window_reads_zero(self):
        s = InvokeStats()
        assert s.latency_us == 0
        assert s.throughput_milli == 0
        snap = s.snapshot()
        assert snap["latency_us"] == 0
        assert snap["total_invokes"] == 0

    def test_single_sample_throughput_zero(self):
        s = InvokeStats()
        s.record(0.001, now=100.0)
        assert s.latency_us == 1000
        assert s.throughput_milli == 0  # a rate needs two stamps

    def test_stale_samples_pruned_from_throughput(self):
        s = InvokeStats(max_age_s=10.0)
        s.record(0.001, now=100.0)
        s.record(0.001, now=150.0)  # 50 s later: the first stamp is stale
        assert s.throughput_milli == 0  # only one live stamp remains
        s.record(0.001, now=150.5)
        s.record(0.001, now=151.0)
        # 3 live stamps over 1 s → 2 intervals/s → 2000 milli-out/s
        assert s.throughput_milli == 2000
        assert s.total_invokes == 4  # cumulative count never prunes

    def test_latency_window_bounded(self):
        s = InvokeStats(window=3)
        for lat in (1.0, 1.0, 0.001, 0.001, 0.001):
            s.record(lat, now=100.0)
        # only the last `window` samples feed the average
        assert s.latency_us == 1000
        assert s.total_invokes == 5
        assert abs(s.total_latency_s - 2.003) < 1e-9

    def test_measure_context_manager(self):
        s = InvokeStats()
        with s.measure():
            pass
        assert s.total_invokes == 1
        assert s.latency_us >= 0
