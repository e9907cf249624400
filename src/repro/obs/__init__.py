"""repro.obs — in-loop telemetry: scan-carried theory metrics, round
tracing, and structured sinks.

Three pieces:

* :mod:`repro.obs.metrics` — *what* to record: the paper's per-round
  theory quantities (prox-gradient mapping, consensus errors, tracking
  error, momentum variance) plus cohort size and traced bytes-on-wire.
* :mod:`repro.obs.record` — *how* to record it: a ring buffer riding the
  ``lax.scan`` carry, flushed through ``io_callback`` into sinks, with
  cadence and config tags as runtime operands (zero retraces).
* :mod:`repro.obs.trace` / :mod:`repro.obs.sinks` — named scopes on
  device ops, profiler spans on host time, blocked-vs-dispatch timing, and
  the pluggable JSONL / CSV / in-memory event sinks.

Attributes resolve lazily (PEP 562): ``repro.core`` modules annotate
their phases via :mod:`repro.obs.trace` while :mod:`repro.obs.metrics`
imports them back — lazy resolution keeps that pair acyclic.
"""
import importlib

#: public name -> defining submodule
_EXPORTS = {
    "DEFAULT_METRICS": "metrics", "MetricSpec": "metrics",
    "prox_gap_sq": "metrics", "round_values": "metrics",
    "traced_payload_row_bytes": "metrics", "traced_round_bytes": "metrics",
    "tracking_error": "metrics",
    "Telemetry": "record", "TelemetryCarry": "record",
    "CsvSink": "sinks", "JsonlSink": "sinks", "MemorySink": "sinks",
    "validate_event": "sinks", "validate_jsonl": "sinks",
    "PHASES": "trace", "Timing": "trace", "annotate": "trace",
    "profile_capture": "trace", "span": "trace", "time_fn": "trace",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name):
    module = _EXPORTS.get(name)
    if module is None:
        raise AttributeError(f"module 'repro.obs' has no attribute {name!r}")
    value = getattr(importlib.import_module(f"repro.obs.{module}"), name)
    globals()[name] = value
    return value


def __dir__():
    return sorted(set(globals()) | set(__all__))
