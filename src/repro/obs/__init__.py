"""Engine observability: jit-safe counters, stage tracing, metrics registry,
exporters.

- :mod:`repro.obs.counters` — device-side counter pytrees threaded through
  streaming carries, the pane store, and the shard combine tree; surfaced
  as ``AggResult.stats`` / ``StreamResult.stats`` via
  ``execute(..., collect_stats=True)``.
- :mod:`repro.obs.trace` — device stage scopes (``trace.stage(name)``,
  named in the compiled program's ``op_name`` metadata) and host spans on
  the profiler's clock around plan / partition / local / merge / finalize
  / dispatch (``with trace.capture() as tr: ...`` records their host
  time).
- :mod:`repro.obs.registry` — process-wide per-(backend, plan fingerprint)
  observed tuples/s, the measured-cost routing table.
- :mod:`repro.obs.export` — JSONL and Prometheus text exporters.
"""
from repro.obs import counters, export, trace
from repro.obs.export import (dumps_jsonl, prometheus_metrics, read_jsonl,
                              to_jsonable, write_jsonl)
from repro.obs.registry import (METRICS, MetricsRegistry, get_registry,
                                plan_fingerprint)
from repro.obs.trace import Tracer, capture, span
