"""obs — process-wide metrics registry + live telemetry export.

The reference measures itself per-filter at runtime (``latency`` /
``throughput`` properties, tensor_filter.c:325-423) and defers
pipeline-level visibility to external GstShark tracers. This package is
the pipeline-wide half, in-tree: every hot path (queue depth/drops,
rate drops, mux/merge sync wait, filter invokes, serving dispatches,
query/gRPC traffic) reports into ONE thread-safe registry with a stable
naming scheme::

    nns_<element>_<metric>{pipeline="...", element="..."}

and the registry exports three ways:

- :class:`MetricsServer` — HTTP endpoint serving Prometheus text
  exposition (``/metrics``) and a JSON snapshot (``/metrics.json``);
- ``Pipeline.metrics_snapshot()`` — in-process structured read;
- ``nns-launch --metrics-port`` — CLI wiring plus a post-EOS
  per-element table with drops and e2e p50/p99.

Per-element numbers are sampled from the SAME :class:`InvokeStats`
windows that back the element ``latency``/``throughput`` properties, so
the exported gauges always agree with the in-band read-outs.
"""

# FIRST import, before any sibling that creates module-level locks
# (registry's process registry, the flight recorder): when
# NNSTPU_LOCKGRAPH is set the lock factories must already be patched
# by the time those locks are created, or the witness misses them
from nnstreamer_tpu.obs import lockgraph  # noqa: F401
lockgraph.maybe_activate_env()

from nnstreamer_tpu.obs.registry import (  # noqa: E402,F401
    Counter,
    Gauge,
    Histogram,
    LATENCY_BUCKETS_S,
    MetricsRegistry,
    get_registry,
)
from nnstreamer_tpu.obs.collectors import (  # noqa: F401
    register_engine_collector,
    register_pipeline_collector,
)
from nnstreamer_tpu.obs.server import MetricsServer  # noqa: F401
from nnstreamer_tpu.obs.timeline import (  # noqa: F401
    TRACE_SEQ_META,
    Timeline,
    device_trace,
    trace_enabled,
    tracing,
)
from nnstreamer_tpu.obs.quantiles import (  # noqa: F401
    BurnRateWindow,
    P2Quantile,
)
from nnstreamer_tpu.obs.flight import (  # noqa: F401
    FlightRecorder,
    flight_enabled,
)
