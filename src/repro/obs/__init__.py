"""repro.obs — one telemetry spine for every execution path.

Layers (docs/observability.md):
  gauges    jit-safe in-graph reductions + host meters (wire bytes,
            device memory) — the single source both runtimes read
  graph     collaboration-graph gauges: contraction estimate, per-edge
            attribution, similarity — §Graph diagnostics
  record    versioned record schema (round/tick/serve/graph/alert)
  sink      MetricsSink protocol: Null / Ring / Jsonl / Tee
  flight    FlightRecorder sink wrapper: anomaly gates + post-mortems
  profiler  maybe_trace (jax.profiler), span (host spans on the trace's
            clock), PhaseTimer (perf_counter + spans), compile_count
  report    `python -m repro.obs.report run.jsonl [--check|--graph|
            --diff|--postmortem]`

Instrumentation is OFF by default and gated by `AlgoSpec.telemetry`;
the uninstrumented round is bit-for-bit identical (tests/test_obs.py).
"""
from repro.obs import gauges, record
from repro.obs.flight import FlightRecorder
from repro.obs.gauges import accounted_bytes, peak_device_memory
from repro.obs.profiler import PhaseTimer, compile_count, maybe_trace, span
from repro.obs.record import (SCHEMA_VERSION, alert_record, graph_record,
                              round_record, serve_record, tick_record)
from repro.obs.sink import (NULL_SINK, JsonlSink, MetricsSink, NullSink,
                            RingSink, TeeSink)

__all__ = [
    "gauges", "record",
    "accounted_bytes", "peak_device_memory",
    "PhaseTimer", "maybe_trace", "span", "compile_count",
    "SCHEMA_VERSION", "round_record", "tick_record", "serve_record",
    "graph_record", "alert_record",
    "MetricsSink", "NullSink", "RingSink", "JsonlSink", "TeeSink",
    "NULL_SINK", "FlightRecorder",
]
