"""Exporters: JSON metrics snapshot + Chrome-trace timeline.

* :func:`metrics_snapshot` — the process-wide registry as one JSON-safe
  dict (the ``/metrics`` endpoint body, merged with per-model serve
  stats by the HTTP front end).
* :func:`chrome_trace` — captured spans/events as ``trace_event`` JSON
  (the Trace Event Format consumed by ``chrome://tracing`` and
  Perfetto's legacy importer): complete ``"ph": "X"`` events with
  microsecond ``ts``/``dur``, one ``tid`` lane per thread, span labels
  in ``args``. Thread-name metadata events give lanes readable names.
  Request-scoped trace ids (``obs/context.py``) additionally render as
  **flow events** (``ph: "s"/"t"/"f"``): one flow per request, stepping
  through every span that carries its trace id — so the fan-in of N
  admitted requests into one bucket-batch span and the fan-out back to
  their per-request completions draw as arrows across lanes.
  Host spans recorded while ``enable(device_annotations=True)`` also
  entered ``jax.profiler`` annotations, so a simultaneous XProf capture
  carries the same names on its device timeline — load both traces in
  Perfetto to correlate.
* :func:`prometheus_text` — one or more metrics registries in the
  Prometheus text exposition format (the ``/metrics`` endpoint body
  under ``Accept: text/plain`` content negotiation), so standard
  scrapers consume the same registry the JSON snapshot serves.
"""

from __future__ import annotations

import json
import math
import os
import re
from typing import Any

from mmlspark_tpu.obs import runtime as _rt
from mmlspark_tpu.obs.events import EventRecord, SpanRecord
from mmlspark_tpu.obs.metrics import (
    Counter, Gauge, Histogram, MetricsRegistry, registry,
)


def metrics_snapshot() -> dict:
    """The default registry + tracer state, JSON-safe."""
    return {
        "enabled": _rt.enabled(),
        "captured_spans": _rt.captured_count(),
        "metrics": registry().snapshot(),
    }


def _args(labels: dict | None) -> dict:
    if not labels:
        return {}
    return {str(k): (v if isinstance(v, (int, float, str, bool))
                     or v is None else str(v))
            for k, v in labels.items()}


# serve-replica spans get their own synthetic timeline lane (one per
# (model, replica index)) so the DP fan-out's concurrency is visible
# directly — the base is far above any real thread id's useful range and
# stable across exports; the model digest keeps two sharded models'
# replica-0 lanes from colliding onto one tid (Perfetto derives span
# nesting from interval containment per tid)
REPLICA_TID_BASE = 1 << 31
_REPLICA_LANE_STRIDE = 4096


def _record_lane(r: Any) -> tuple[int, str]:
    """(tid, lane name) for one record: spans labeled with a ``replica``
    index land on a dedicated per-(model, replica) lane instead of their
    worker thread's, so a dp=N model renders as N parallel lanes."""
    labels = getattr(r, "labels", None)
    if labels:
        rep = labels.get("replica")
        if rep is not None:
            try:
                idx = int(rep)
            except (TypeError, ValueError):
                return r.tid, r.thread_name
            import zlib
            model = str(labels.get("model", ""))
            digest = zlib.crc32(model.encode("utf-8")) % _REPLICA_LANE_STRIDE
            tid = (REPLICA_TID_BASE + digest * _REPLICA_LANE_STRIDE
                   + idx % _REPLICA_LANE_STRIDE)
            name = (f"serve-replica-{idx}" if not model
                    else f"serve-replica-{idx} [{model}]")
            return tid, name
    return r.tid, r.thread_name


def chrome_trace(records: list | None = None) -> dict:
    """``{"traceEvents": [...]}`` for the given records (default: the
    runtime ring buffer). Spans become complete events (``ph: "X"``)
    whose nesting Perfetto derives from interval containment per
    ``tid``; instants become ``ph: "i"`` thread-scoped events.
    Replica-labeled serve spans render one lane per replica
    (:func:`_record_lane`), and request trace ids render as flow
    events (:func:`_flow_events`) so one request's journey draws as
    arrows across lanes. ``ts`` stays on the ``perf_counter`` clock;
    ``otherData.epoch_offset_ns`` is what to add to ``ts * 1000`` for
    Unix-epoch nanoseconds (``obs/runtime.to_epoch_ns``)."""
    if records is None:
        records = _rt.spans()
    pid = os.getpid()
    events: list[dict] = []
    thread_names: dict[int, str] = {}
    # trace id -> the spans carrying it (own trace or links), with the
    # lane each renders on — the flow-event pass below walks these
    flows: dict[int, list[tuple[SpanRecord, int]]] = {}
    for r in records:
        tid, lane = _record_lane(r)
        thread_names.setdefault(tid, lane)
        if isinstance(r, SpanRecord):
            events.append({
                "name": r.name, "cat": r.cat, "ph": "X",
                "ts": r.start_ns / 1e3, "dur": r.dur_ns / 1e3,
                "pid": pid, "tid": tid,
                "args": {**_args(r.labels), "span_id": r.span_id,
                         **({"parent_id": r.parent_id}
                            if r.parent_id is not None else {}),
                         **({"trace": r.trace}
                            if r.trace is not None else {}),
                         **({"links": list(r.links)} if r.links else {})},
            })
            if r.trace is not None:
                flows.setdefault(r.trace, []).append((r, tid))
            for link in r.links or ():
                flows.setdefault(link, []).append((r, tid))
        elif isinstance(r, EventRecord):
            events.append({
                "name": r.name, "cat": r.cat, "ph": "i", "s": "t",
                "ts": r.ts_ns / 1e3, "pid": pid, "tid": tid,
                "args": _args(r.labels),
            })
    events.extend(_flow_events(flows, pid))
    for tid, tname in thread_names.items():
        events.append({
            "name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
            "args": {"name": tname},
        })
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "otherData": {"epoch_offset_ns": _rt.to_epoch_ns(0)}}


def _flow_events(flows: dict[int, list[tuple[SpanRecord, int]]],
                 pid: int) -> list[dict]:
    """Perfetto flow events for the request traces: per trace id, a
    flow start (``ph: "s"``) anchored in its first span, a step
    (``"t"``) in every intermediate span, and a finish (``"f"``) in the
    last — each bound to its enclosing slice (``bp: "e"``, timestamp at
    the span's midpoint so the binding is unambiguous). In the Perfetto
    UI this draws the admission → pack → dispatch → drain → complete
    arrows of one request across the scheduler/lane/replica lanes —
    including the N-into-1 fan-in at pack and the 1-into-N fan-out at
    completion, because batch spans participate in every linked flow."""
    out: list[dict] = []
    for flow_id, touched in flows.items():
        if len(touched) < 2:
            continue  # an arrow needs two ends
        touched = sorted(touched, key=lambda t: (t[0].start_ns,
                                                 t[0].span_id))
        last = len(touched) - 1
        for i, (r, tid) in enumerate(touched):
            out.append({
                "name": "request", "cat": "serve.request",
                "ph": "s" if i == 0 else ("f" if i == last else "t"),
                "id": flow_id, "bp": "e",
                "ts": (r.start_ns + r.dur_ns / 2) / 1e3,
                "pid": pid, "tid": tid,
            })
    return out


def write_chrome_trace(path: str, records: list | None = None) -> str:
    """Serialize :func:`chrome_trace` to ``path``; returns the path."""
    payload = chrome_trace(records)
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(payload, fh)
    return path


def write_snapshot(path: str) -> str:
    """Serialize :func:`metrics_snapshot` to ``path``; returns the path."""
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(metrics_snapshot(), fh, indent=2, default=str)
    return path


def summarize_spans(records: list | None = None,
                    top: int = 20) -> list[dict]:
    """Aggregate spans by name: calls, total/mean ms — the CLI's text
    timeline (``tools/trace.py render``)."""
    if records is None:
        records = _rt.spans()
    agg: dict[str, dict[str, Any]] = {}
    for r in records:
        if not isinstance(r, SpanRecord):
            continue
        row = agg.setdefault(r.name, {"name": r.name, "cat": r.cat,
                                      "calls": 0, "total_ms": 0.0})
        row["calls"] += 1
        row["total_ms"] += r.dur_ns / 1e6
    rows = sorted(agg.values(), key=lambda d: -d["total_ms"])[:top]
    for row in rows:
        row["total_ms"] = round(row["total_ms"], 3)
        row["mean_ms"] = round(row["total_ms"] / row["calls"], 3)
    return rows


# ---- Prometheus text exposition (the /metrics content-negotiated body) ----

_PROM_NAME_BAD = re.compile(r"[^a-zA-Z0-9_:]")

# registry-series name → HELP text. Keyed by the ORIGINAL (dotted)
# name; anything not listed falls back to a generic line, so every
# family always carries a HELP/TYPE pair (some scrapers and linters —
# promtool check metrics — warn on HELP-less families). Keep entries
# one-line: the exposition format ends HELP at the newline.
METRIC_HELP: dict[str, str] = {
    "plan.h2d_uploads": "Host-to-device uploads issued by the device "
                        "plan executor (one per fused-segment entry).",
    "plan.h2d_bytes": "Bytes shipped host-to-device at the plan's "
                      "upload seam.",
    "plan.split_minibatches": "Minibatches of batch transform calls "
                              "dispatched in row pieces (a long upload).",
    "plan.d2h_fetches": "Async device-to-host fetch rounds issued by "
                        "the plan executor.",
    "plan.d2h_bytes": "Bytes fetched device-to-host at the plan's "
                      "fetch seam.",
    "plan.segment_compiles": "Fresh XLA compilations observed at the "
                             "plan dispatch seam.",
    "serve.queue_depth": "Live admission-queue depth (the replica "
                         "autoscaling signal).",
    "serve.slo_burn_short": "Error-budget burn multiple over the SLO's "
                            "short window (fast-burn page signal).",
    "serve.slo_burn_long": "Error-budget burn multiple over the SLO's "
                           "long window (sustained degradation).",
    "serve.slo_budget_remaining": "Fraction of the SLO error budget "
                                  "remaining (lifetime).",
    "serve.occupancy_mean_window": "Mean batch occupancy over the SLO "
                                   "sample window (adaptive-ladder "
                                   "signal).",
    "serve.replica_skew": "DP replica load imbalance: (max-min)/max "
                          "over per-replica batch counts.",
    "train.steps": "Optimizer steps completed by the training loop.",
    "train.step_ms": "Per-step dispatch time of the training loop.",
    "train.host_step_ms": "Per-host mean step time from the fenced "
                          "liveness exchange (straggler sensor).",
    "train.host_skew": "Max/median host step-time skew across the "
                       "training fleet.",
    "train.slow_steps": "Steps flagged slower than factor x the "
                        "rolling median.",
    "train.fleet.workers": "Live supervised workers reporting a "
                           "current-generation beacon.",
    "train.fleet.progress": "Summed progress (heartbeats + steps) "
                            "across the supervised fleet.",
    "train.fleet.straggler_windows": "Global straggler verdict windows "
                                     "this generation (max across "
                                     "beacons).",
    "train.fleet.host_step_ms": "Per-host step time as aggregated by "
                                "the supervisor from worker beacons.",
    "flight.dumps": "Post-mortem dumps written by the flight recorder.",
    "obs.traces_dropped": "Request traces evicted by the retention "
                          "policy.",
}


def _prom_help(original_name: str) -> str:
    text = METRIC_HELP.get(original_name)
    if text is None:
        # generic fallback: every family gets SOME help line, and the
        # original dotted spelling survives sanitization for operators
        # grepping the codebase
        text = f"mmlspark_tpu metric {original_name} (see " \
               "docs/observability.md)."
    return text.replace("\\", r"\\").replace("\n", r"\n")


def _prom_name(name: str) -> str:
    """Registry series name → a legal Prometheus metric name (dots and
    other separators become underscores; a leading digit is prefixed)."""
    name = _PROM_NAME_BAD.sub("_", name)
    if name and name[0].isdigit():
        name = "_" + name
    return name


def _prom_labels(labels: tuple, extra: tuple = ()) -> str:
    """``(k, v)`` label pairs → ``{k="v",...}`` with value escaping per
    the exposition format (backslash, quote, newline)."""
    pairs = tuple(labels) + tuple(extra)
    if not pairs:
        return ""
    parts = []
    for k, v in pairs:
        val = str(v).replace("\\", r"\\").replace('"', r"\"")
        val = val.replace("\n", r"\n")
        parts.append(f'{_prom_name(str(k))}="{val}"')
    return "{" + ",".join(parts) + "}"


def _prom_value(v: float) -> str:
    v = float(v)
    if math.isnan(v):
        # the registry is the shared substrate — one client recording a
        # NaN/Inf (zero-denominator ratio, say) must not 500 the whole
        # scrape; these are the official Prometheus text literals
        return "NaN"
    if math.isinf(v):
        return "+Inf" if v > 0 else "-Inf"
    if v == int(v):
        return str(int(v))
    return repr(v)


def prometheus_text(registries: list[MetricsRegistry] | None = None) -> str:
    """Every metric of the given registries (default: the process-wide
    one) in the Prometheus text exposition format (version 0.0.4).

    Counters/gauges map directly; histograms expose as summaries —
    ``name{quantile="0.5|0.95|0.99"}`` over the bounded window plus the
    exact lifetime ``name_count``/``name_sum``. ONE ``# HELP``/``# TYPE``
    header pair is emitted per metric name across all registries
    (per-model serve registries — and the fleet-merged per-host
    registries — contribute the same names under different labels;
    repeating a header per registry is an exposition-format violation
    scrapers reject). HELP text comes from :data:`METRIC_HELP` with a
    generic fallback, so every family is self-describing. Unset gauges
    are skipped (Prometheus has no null). Series within a name are
    emitted in sorted order so consecutive scrapes of the same state
    are byte-identical."""
    if registries is None:
        registries = [registry()]
    # prom name -> [type string, [(series text, value)], original name]
    by_name: dict[str, list] = {}

    def _add(name: str, original: str, kind: str,
             lines: list[tuple[str, str]]) -> None:
        slot = by_name.setdefault(name, [kind, [], original])
        slot[1].extend(lines)

    for reg in registries:
        for m in reg.iter_metrics():
            name = _prom_name(m.name)
            if isinstance(m, Counter):
                _add(name, m.name, "counter",
                     [(f"{name}{_prom_labels(m.labels)}",
                       _prom_value(m.value))])
            elif isinstance(m, Gauge):
                v = m.value
                if v is None:
                    continue
                _add(name, m.name, "gauge",
                     [(f"{name}{_prom_labels(m.labels)}",
                       _prom_value(v))])
            elif isinstance(m, Histogram):
                pct = m.percentiles(ndigits=None)
                lines = []
                if pct is not None:
                    for q, key in (("0.5", "p50"), ("0.95", "p95"),
                                   ("0.99", "p99")):
                        lines.append((
                            f"{name}"
                            f"{_prom_labels(m.labels, (('quantile', q),))}",
                            _prom_value(pct[key])))
                lines.append((f"{name}_count{_prom_labels(m.labels)}",
                              _prom_value(m.count)))
                lines.append((f"{name}_sum{_prom_labels(m.labels)}",
                              _prom_value(m.sum)))
                _add(name, m.name, "summary", lines)
    chunks: list[str] = []
    for name in sorted(by_name):
        kind, lines, original = by_name[name]
        chunks.append(f"# HELP {name} {_prom_help(original)}")
        chunks.append(f"# TYPE {name} {kind}")
        chunks.extend(f"{series} {value}" for series, value
                      in sorted(lines))
    return "\n".join(chunks) + ("\n" if chunks else "")
