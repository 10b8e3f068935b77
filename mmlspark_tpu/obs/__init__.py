"""Unified observability — structured tracing, metrics, timeline export.

The reference's observability story is wall-clock logging (the ``Timer``
stage, reference: pipeline-stages/src/main/scala/Timer.scala:54-123). This
repo's hot paths — the fused device plan (``core/plan.py``), the
prefetching train input pipeline (``train/input.py``), and the
dynamic-batching server (``serve/``) — each grew their own accounting;
this package is the ONE telemetry substrate they all record into, in the
spirit of Dapper-style span tracing and the XProf/Perfetto device
timeline:

* :mod:`~mmlspark_tpu.obs.metrics` — a process-wide, thread-safe
  **metrics registry**: counters, gauges, and windowed histograms
  (p50/p95/p99), labeled (model/stage/bucket/loader).
* :mod:`~mmlspark_tpu.obs.spans` — a **structured span/event tracer**:
  nested spans with wall + thread timestamps into a bounded ring buffer.
  Disabled (the default) it is a single module-level flag check returning
  a shared null context — no allocation, no locking.
* :mod:`~mmlspark_tpu.obs.export` — **exporters**: a JSON metrics
  snapshot and Chrome-trace/Perfetto ``trace_event`` JSON; host spans can
  additionally enter ``jax.profiler`` annotations
  (``enable(device_annotations=True)``) so an XProf capture interleaves
  them with the device timeline.
* :mod:`~mmlspark_tpu.obs.runtime` — enable/disable plus the jit
  compile-cache hook (promoted here from the serve layer).
* :mod:`~mmlspark_tpu.obs.context` — **request-scoped tracing**: trace
  ids minted at admission, bound across thread hops, fan-in/fan-out
  span links, and the ``request_traces``/``check_journey`` read side.
* :mod:`~mmlspark_tpu.obs.slo` — the **SLO engine**: declarative
  objectives (``SLOSpec``), windowed error-budget burn rates computed
  from registry reads only (``SLOTracker``), and the train-loop
  slow-step detector.
* :mod:`~mmlspark_tpu.obs.health` — the **ok/degraded/unhealthy state
  machine** (fast/slow burn + reject-ratio classification, hysteretic
  recovery) behind the serving health surfaces.
* :mod:`~mmlspark_tpu.obs.flight` — the **flight recorder**: an
  always-on post-mortem ring + watchdog that dumps recent spans,
  per-thread stacks, and the registry snapshot on crash, signal, or
  hang (``MMLSPARK_TPU_FLIGHT=<dir>``).
* :mod:`~mmlspark_tpu.obs.device` — **device attribution**: per-segment
  compile-time histograms, XLA cost/memory gauges
  (``plan.segment.*``), live device-memory polling, and the host
  phase split over the always-on boundary spans.
* :mod:`~mmlspark_tpu.obs.compile_tier` — the **compile tier**, always
  on: every trace, lowering and backend compile (or persistent-cache
  load) JAX makes, by function, and the collector's pauses, as records
  in the same ring on the same clock; ``compile_report()`` is the table
  of what building programs cost.
* :mod:`~mmlspark_tpu.obs.anomaly` — the **train anomaly plane**:
  non-finite loss sentinel (typed :class:`NonFiniteLossError`) and
  multi-host straggler detection (``train.host_skew``).
* :mod:`~mmlspark_tpu.obs.fleet` — the **fleet telemetry plane**:
  per-process atomic snapshot export (``MMLSPARK_TPU_FLEET=<dir>``),
  cross-process registry merge (counters summed bit-exactly, gauges
  per host), and the clock-aligned fleet Perfetto timeline stitched
  at the fenced-collective seams.
* :mod:`~mmlspark_tpu.obs.timeseries` — **metric history**: a periodic
  sampler persisting the SLO/autoscale gauges into a bounded ring +
  append-only JSONL with a small query API (``range``/``rate``/
  ``last``) — the trend signals the adaptive ladder and autoscalers
  need.
* :mod:`~mmlspark_tpu.obs.lockwitness` — the **runtime lock-order
  witness**: ``named_lock``/``named_rlock``/``named_condition``
  factories whose name strings join the static lock-order graph of
  :mod:`mmlspark_tpu.analysis.concurrency`; opt-in edge recording,
  both-order violation detection, and ``crosscheck`` labelling of
  static edges (docs/concurrency.md).

Everything is CPU-safe and jax-free at import time. See
docs/observability.md for the architecture and the instrumented seams.
"""

from mmlspark_tpu.obs.events import EventRecord, SpanRecord  # noqa: F401
from mmlspark_tpu.obs.metrics import (  # noqa: F401
    Counter, Gauge, Histogram, MetricsRegistry, registry,
)
from mmlspark_tpu.obs.runtime import (  # noqa: F401
    clear, compiled_programs, disable, enable, enabled,
)
from mmlspark_tpu.obs.runtime import spans as captured  # noqa: F401
from mmlspark_tpu.obs.spans import boundary_span, event, span  # noqa: F401
from mmlspark_tpu.obs.context import (  # noqa: F401
    REQUEST_JOURNEY, bind, check_journey, mint, request_traces,
)
from mmlspark_tpu.obs.export import (  # noqa: F401
    chrome_trace, metrics_snapshot, prometheus_text, write_chrome_trace,
    write_snapshot,
)
from mmlspark_tpu.obs.slo import (  # noqa: F401
    SLOSpec, SLOTracker, SlowStepDetector,
)
from mmlspark_tpu.obs.health import (  # noqa: F401
    HealthMonitor, HealthPolicy,
)
from mmlspark_tpu.obs import anomaly  # noqa: F401
from mmlspark_tpu.obs import compile_tier  # noqa: F401
from mmlspark_tpu.obs import device  # noqa: F401
from mmlspark_tpu.obs import fleet  # noqa: F401
from mmlspark_tpu.obs import flight  # noqa: F401
from mmlspark_tpu.obs import lockwitness  # noqa: F401
from mmlspark_tpu.obs import timeseries  # noqa: F401
from mmlspark_tpu.obs.anomaly import (  # noqa: F401
    NonFiniteLossError, NonFiniteSentinel, StragglerDetector,
)
from mmlspark_tpu.obs.compile_tier import (  # noqa: F401
    compile_report, gc_records_dropped,
)
from mmlspark_tpu.obs.device import (  # noqa: F401
    host_phase_split, poll_memory,
)

# the compile tier is always on: the collector's callback now, JAX's
# listeners too when jax is already imported (obs never imports it
# first; utils/jit_cache.place_compilation_cache registers otherwise)
compile_tier.register()

__all__ = [
    "Counter",
    "EventRecord",
    "Gauge",
    "HealthMonitor",
    "HealthPolicy",
    "Histogram",
    "MetricsRegistry",
    "NonFiniteLossError",
    "NonFiniteSentinel",
    "REQUEST_JOURNEY",
    "SLOSpec",
    "SLOTracker",
    "SlowStepDetector",
    "SpanRecord",
    "StragglerDetector",
    "anomaly",
    "bind",
    "boundary_span",
    "captured",
    "check_journey",
    "chrome_trace",
    "clear",
    "compile_report",
    "compile_tier",
    "compiled_programs",
    "device",
    "disable",
    "enable",
    "enabled",
    "event",
    "fleet",
    "flight",
    "gc_records_dropped",
    "host_phase_split",
    "lockwitness",
    "metrics_snapshot",
    "mint",
    "poll_memory",
    "prometheus_text",
    "registry",
    "request_traces",
    "span",
    "spans",
    "timeseries",
    "write_chrome_trace",
    "write_snapshot",
]
