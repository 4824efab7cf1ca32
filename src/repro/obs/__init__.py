"""Unified observability layer (DESIGN.md §14).

Three pillars, all off by default and structurally zero-cost when off:

* `obs.metrics`   — process-wide registry of counters / gauges /
  histograms with labels, plus THE single `latency_percentiles`
  definition shared by serving, scheduling and the benches.
* `obs.trace`     — nestable span tracing (context manager + decorator,
  monotonic clock, thread-safe) exporting Chrome-trace/Perfetto JSON;
  every span is also a `jax.profiler.TraceAnnotation`, so a profiler
  session shows it on the device ops' clock.
* `obs.telemetry` — per-epoch training telemetry (loss, update norms,
  DP ε trajectory, churn online counts, DelayRing occupancy, Byzantine
  screening counts, messages per shard) assembled host-side from
  fixed-shape device reductions threaded through the epoch scan.

The hard contract mirrors the byzantine layer's: instrumentation off is
the statically-dead-code default (bit-exact with the uninstrumented
stack at every shard count), and telemetry on leaves factor
trajectories bit-identical — reductions only, no extra rng draws.
"""
from repro.obs.metrics import (MetricsRegistry, get_registry,   # noqa: F401
                               latency_percentiles, set_registry)
from repro.obs.trace import (Tracer, configure_tracing,          # noqa: F401
                             get_tracer, set_tracer, span)
from repro.obs.telemetry import (EpochCollector, TELE_KEYS,      # noqa: F401
                                 TELE_W, device_stats_to_dict)
