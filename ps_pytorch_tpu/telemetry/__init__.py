"""Unified telemetry: span tracing (trace.py), typed metric registry with
MFU/goodput derivation (registry.py), cross-host step aggregation over the
control-plane KV (aggregate.py), and the live ops plane — Prometheus
exposition/exporter (prometheus.py), training-health watchdogs (health.py),
sliding-window SLO burn-rate evaluation (slo.py), and the crash-dump
flight recorder (flightrec.py). See each module's docstring."""

from ps_pytorch_tpu.telemetry.aggregate import (  # noqa: F401
    TelemetryAggregator, read_timeline,
)
from ps_pytorch_tpu.telemetry.flightrec import (  # noqa: F401
    FlightRecorder, load_flight,
)
from ps_pytorch_tpu.telemetry.health import (  # noqa: F401
    HealthEvent, HealthMonitor, parse_health_spec,
)
from ps_pytorch_tpu.telemetry.prometheus import (  # noqa: F401
    MetricsExporter, parse_exposition, render as render_prometheus,
    sanitize_name,
)
from ps_pytorch_tpu.telemetry.registry import (  # noqa: F401
    HIERARCHY_COUNTERS, HIERARCHY_GAUGES, INTEGRITY_COUNTERS,
    INTEGRITY_GAUGES, KVREP_COUNTERS, KVREP_GAUGES, RESILIENCE_COUNTERS,
    SERVING_COUNTERS, SERVING_GAUGES,
    SERVING_HISTOGRAMS, TRAINING_COUNTERS, TRAINING_GAUGES,
    TRAINING_HISTOGRAMS, MetricSpec, Registry, compute_mfu, data_stall_fraction, declare_elastic_metrics,
    declare_hierarchy_metrics, declare_integrity_metrics,
    declare_kvrep_metrics, declare_resilience_metrics,
    declare_serving_metrics, declare_training_metrics, derive_step_record,
    device_memory_record, host_rss_bytes, set_device_memory_gauges,
)
from ps_pytorch_tpu.telemetry.slo import (  # noqa: F401
    SLOObjective, SLOTracker, WindowPercentile, check_slo, parse_slo_spec,
)
from ps_pytorch_tpu.telemetry.trace import (  # noqa: F401
    ProfileWindow, Tracer, get_default_tracer, latest_tracer, self_times,
    set_default_tracer, span, startup_line,
)
