"""Trace records — the data the span tracer writes and exporters read.

Plain slotted records (no dataclass machinery on the hot path) holding
wall timestamps in integer nanoseconds (``time.perf_counter_ns`` epoch —
monotonic, comparable across threads of one process) plus the thread
identity Chrome-trace lanes group by. ``start_epoch_ns``/``end_epoch_ns``
give the same instants on the Unix epoch (``time.time_ns``), the clock
a profiler session's start is stamped with, through the runtime's one
anchor pair.
"""

from __future__ import annotations

from typing import Any


class SpanRecord:
    """One completed span: a named, labeled interval on one thread.

    ``trace`` and ``links`` are the request-scoped tracing fields
    (``obs/context.py``): ``trace`` is the request trace id the span was
    recorded under (inherited from the thread's active request context),
    and ``links`` is the tuple of OTHER trace ids a fan-in/fan-out span
    touches (a bucket-batch span links every coalesced request's trace).
    Both default to None so nesting/threading stay unchanged for spans
    recorded outside any request.

    ``root_id``, ``rows``, ``nbytes`` and ``minibatches`` belong to the
    boundary tier (``obs/spans.boundary_span``, recorded with the tracer
    off too): ``root_id`` is the outermost boundary span open on the
    thread — the identifier the spans of one ``transform`` call share,
    the root's own ``span_id`` on the root — and the three integers are
    what the seam already had in hand. All None on a gated span.
    """

    __slots__ = ("name", "cat", "start_ns", "dur_ns", "tid", "thread_name",
                 "span_id", "parent_id", "depth", "labels", "trace",
                 "links", "root_id", "rows", "nbytes", "minibatches")

    def __init__(self, name: str, cat: str, start_ns: int, dur_ns: int,
                 tid: int, thread_name: str, span_id: int,
                 parent_id: int | None, depth: int,
                 labels: dict | None, trace: int | None = None,
                 links: tuple | None = None, root_id: int | None = None,
                 rows: int | None = None, nbytes: int | None = None,
                 minibatches: int | None = None):
        self.name = name
        self.cat = cat
        self.start_ns = start_ns
        self.dur_ns = dur_ns
        self.tid = tid
        self.thread_name = thread_name
        self.span_id = span_id
        self.parent_id = parent_id
        self.depth = depth
        self.labels = labels
        self.trace = trace
        self.links = links
        self.root_id = root_id
        self.rows = rows
        self.nbytes = nbytes
        self.minibatches = minibatches

    @property
    def end_ns(self) -> int:
        return self.start_ns + self.dur_ns

    @property
    def start_epoch_ns(self) -> int:
        from mmlspark_tpu.obs.runtime import to_epoch_ns
        return to_epoch_ns(self.start_ns)

    @property
    def end_epoch_ns(self) -> int:
        from mmlspark_tpu.obs.runtime import to_epoch_ns
        return to_epoch_ns(self.end_ns)

    def to_dict(self) -> dict[str, Any]:
        out = {
            "name": self.name, "cat": self.cat,
            "start_ns": self.start_ns, "dur_ns": self.dur_ns,
            "tid": self.tid, "thread_name": self.thread_name,
            "span_id": self.span_id, "parent_id": self.parent_id,
            "depth": self.depth, "labels": self.labels or {},
            "trace": self.trace,
            "links": list(self.links) if self.links else [],
        }
        if self.root_id is not None:
            out["root_id"] = self.root_id
            for key in ("rows", "nbytes", "minibatches"):
                if getattr(self, key) is not None:
                    out[key] = getattr(self, key)
        return out

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"SpanRecord({self.name!r}, cat={self.cat!r}, "
                f"dur={self.dur_ns / 1e6:.3f}ms, depth={self.depth})")


class EventRecord:
    """One instant event (a point, not an interval) on one thread."""

    __slots__ = ("name", "cat", "ts_ns", "tid", "thread_name", "labels")

    def __init__(self, name: str, cat: str, ts_ns: int, tid: int,
                 thread_name: str, labels: dict | None):
        self.name = name
        self.cat = cat
        self.ts_ns = ts_ns
        self.tid = tid
        self.thread_name = thread_name
        self.labels = labels

    def to_dict(self) -> dict[str, Any]:
        return {
            "name": self.name, "cat": self.cat, "ts_ns": self.ts_ns,
            "tid": self.tid, "thread_name": self.thread_name,
            "labels": self.labels or {},
        }

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"EventRecord({self.name!r}, cat={self.cat!r})"
