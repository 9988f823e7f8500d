"""Engine-wide observability: tracing spans, metrics, workload recording.

The runtime counterpart to the engine's hard contracts (DESIGN.md §11):

* ``obs.trace``    — nestable spans written into the JAX profiler's trace
  as ``repro.<name>`` host annotations, beside the device's operations
  (a no-op unless a profiler session is active: capture with
  ``jax.profiler.trace(dir, create_perfetto_trace=True)``);
* ``obs.metrics``  — process-local counters / gauges / fixed-bucket
  histograms (p50/p95/p99 without stored samples);
* ``obs.workload`` — bounded recorder of every run/read call's query
  signature, hit path, and latency (the future view advisor's input);
* ``obs.log``      — structured, rate-limited logging.

Design rule shared by all four: **never sync the device**.  Telemetry
reads host clocks around dispatch sites only, so the steady-state
zero-transfer / zero-retrace contracts hold with everything enabled.
"""

from repro.obs.log import StructuredLogger, get_logger
from repro.obs.metrics import (Counter, Gauge, Histogram, Registry,
                               LATENCY_BUCKETS_US)
from repro.obs.trace import span
from repro.obs.workload import (QuerySignature, WorkloadRecord,
                                WorkloadRecorder, agg_renders, routable,
                                signature_of)

__all__ = [
    "span",
    "Counter", "Gauge", "Histogram", "Registry", "LATENCY_BUCKETS_US",
    "QuerySignature", "WorkloadRecord", "WorkloadRecorder", "signature_of",
    "agg_renders", "routable",
    "StructuredLogger", "get_logger",
]
