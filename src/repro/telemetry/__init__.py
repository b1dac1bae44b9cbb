"""`repro.telemetry` — unified metrics + span tracing for train, exchange,
and serve.

Three pieces (see DESIGN.md "Telemetry"):

- a process-local **metrics registry** (counters / gauges / fixed-bucket
  histograms; bounded memory) with pluggable sinks — JSONL file,
  in-memory (tests), periodic console summary;
- a low-overhead **span API** (``with trace.span("exchange/rs",
  bytes=n):``) exporting Chrome-trace/Perfetto JSON;
- one **schema** (versioned, with host/device/backend run context) shared
  by every metrics and trace file a run writes.

Host-side only: instrumentation never adds an op to a jitted program
(grad-norm is the single, explicit opt-in exception). Default-on;
``REPRO_TELEMETRY=0`` switches every accessor to a shared no-op whose
cost is one flag test (its allocation-free path is tested in
``tests/test_telemetry.py``).
"""
from repro.telemetry import metrics, trace
from repro.telemetry import anomaly, profile
from repro.telemetry._runtime import (TelemetryConfig, add_sink,
                                      attach_registry, config, configure,
                                      default_registry, detach_registry,
                                      dump_metrics, enabled, flush, reset,
                                      set_enabled)
from repro.telemetry.registry import (ConsoleSink, Counter, Gauge,
                                      Histogram, Info, JsonlSink,
                                      MemorySink, NOOP, Registry,
                                      TIME_BUCKETS, exp_buckets)
from repro.telemetry.schema import (SCHEMA_VERSION, run_context, run_record,
                                    validate_metrics_jsonl, validate_record,
                                    validate_trace)

__all__ = [
    "metrics", "trace", "anomaly", "profile",
    "TelemetryConfig", "add_sink", "attach_registry", "config", "configure",
    "default_registry", "detach_registry", "dump_metrics", "enabled",
    "flush", "reset", "set_enabled",
    "ConsoleSink", "Counter", "Gauge", "Histogram", "Info", "JsonlSink",
    "MemorySink", "NOOP", "Registry", "TIME_BUCKETS", "exp_buckets",
    "SCHEMA_VERSION", "run_context", "run_record", "validate_metrics_jsonl",
    "validate_record", "validate_trace",
]
