"""Scrape-time collectors bridging live objects into the registry.

The per-element gauges are SAMPLED from each element's ``InvokeStats``
(the object behind the ``latency``/``throughput`` properties) rather
than double-counted on the hot path — the exported numbers therefore
agree with the in-band properties by construction, the consistency rule
the reference keeps between its property read-outs and its internal
framework statistics (tensor_filter.c:325-423).
"""

from __future__ import annotations

import weakref

from nnstreamer_tpu.obs.registry import MetricsRegistry, get_registry


def register_pipeline_collector(pipeline, registry: MetricsRegistry = None
                                ) -> None:
    """Export per-element latency/throughput/invoke gauges for every
    element of ``pipeline``, refreshed at each scrape. Holds only a
    weakref — a garbage-collected pipeline unregisters itself."""
    reg = registry or get_registry()
    ref = weakref.ref(pipeline)

    def collect():
        pipe = ref()
        if pipe is None:
            return False  # subject gone: drop this collector
        for el in pipe.elements:
            labels = {"pipeline": pipe.name, "element": el.name,
                      "type": el.ELEMENT_NAME}
            stats = el._metrics_stats()
            reg.gauge("nns_element_latency_us",
                      "Windowed avg invoke latency (µs), the element "
                      "latency property", **labels).set(stats.latency_us)
            reg.gauge("nns_element_throughput_milli",
                      "Outputs/sec x1000, the element throughput "
                      "property", **labels).set(stats.throughput_milli)
            reg.counter("nns_element_invokes_total",
                        "Cumulative chain invocations",
                        **labels).set_total(stats.total_invokes)
        return True

    reg.register_collector(collect)


def register_engine_collector(engine, registry: MetricsRegistry = None
                              ) -> None:
    """Export the serving engine's cumulative stats + occupancy gauges
    (weakref-bound like the pipeline collector)."""
    reg = registry or get_registry()
    ref = weakref.ref(engine)

    def collect():
        eng = ref()
        if eng is None:
            return False
        labels = {"engine": eng.obs_name}
        reg.gauge("nns_serving_active_streams",
                  "Streams currently holding a batch slot",
                  **labels).set(eng.active_streams)
        reg.gauge("nns_serving_batch_slots", "Configured batch slots (B)",
                  **labels).set(eng.B)
        for key, nbytes in eng.weights.items():
            reg.gauge(f"nns_serving_{key}",
                      "Weights as given to the engine, as it holds them "
                      "(serving_params), and the leaves it narrowed",
                      **labels).set(nbytes)
        win = getattr(eng._pool, "win", None)
        if win is not None:  # a family with window layers: its own arena
            reg.gauge("nns_serving_kv_window_blocks",
                      "Blocks of the window layers' arena",
                      **labels).set(win.num_blocks)
            reg.gauge("nns_serving_kv_window_blocks_live",
                      "Blocks of the window layers' arena that streams "
                      "hold (what lies behind a window has gone back)",
                      **labels).set(win.live_blocks())
        slot_steps = eng.stats["slot_steps"]
        occupancy = (eng.stats["active_slot_steps"] / slot_steps
                     if slot_steps else 0.0)
        reg.gauge("nns_serving_batch_occupancy_ratio",
                  "Fraction of dispatched slot-steps that served a live "
                  "stream", **labels).set(occupancy)
        for key in ("tokens_generated", "dispatches", "prefills",
                    "prefill_chunks", "prefix_hits",
                    "prefix_tokens_reused"):
            reg.counter(f"nns_serving_{key}_total", **labels).set_total(
                eng.stats[key])
        for key, us in list(eng.stats.items()):
            if key.startswith("phase_"):
                reg.counter(
                    "nns_serving_loop_phase_seconds_total",
                    "Engine-loop thread time by phase; the phases tile "
                    "the loop", phase=key[len("phase_"):-len("_us")],
                    **labels).set_total(us / 1e6)
            elif key.startswith("starved_") and key != "starved_us":
                reg.counter(
                    "nns_serving_loop_starved_seconds_total",
                    "The part of a phase in which the loop had nothing "
                    "queued on the device; phase less starved is host "
                    "work hidden behind device work",
                    phase=key[len("starved_"):-len("_us")],
                    **labels).set_total(us / 1e6)
        reg.counter("nns_serving_prefill_programs_total",
                    "Prefill programs of cold admissions; admissions less "
                    "this are the prompts that rode in another's program",
                    **labels).set_total(eng.stats["prefill_programs"])
        reg.counter("nns_serving_emit_blocks_total",
                    "Hand-overs of a program's tokens to a stream, one "
                    "block and one wake-up each: one a stream a dispatch, "
                    "one a first token", **labels).set_total(
                        eng.stats["emit_blocks"])
        return True

    reg.register_collector(collect)
