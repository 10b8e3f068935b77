"""The span/event tracer — nested, thread-aware, near-zero when off.

Usage at an instrumented seam::

    from mmlspark_tpu.obs import span, event

    with span("plan/fused_segment", "plan", {"rows": n}):
        ...
    event("serve/overloaded", "serve")

Disabled (the default), :func:`span` is ONE module-flag check returning a
shared null context — no record, no allocation beyond the call itself.
Enabled, each span captures wall-clock start/duration
(``time.perf_counter_ns``), the owning thread, and its parent span on
that thread (a thread-local stack), then lands in the bounded ring
buffer (:mod:`~mmlspark_tpu.obs.runtime`). Exceptions propagate —
tracing never swallows an error — and the span still records, so a
timeline shows where a run died.

With ``enable(device_annotations=True)`` each span also enters
``jax.profiler.TraceAnnotation`` (via ``utils/profiling.annotate``), so
an XProf/Perfetto device capture shows the same names on its host track,
interleaved with the device ops dispatched under them.

The **boundary tier** (:func:`boundary_span`) is the one exception to
"off unless enabled": a fixed, small set of spans at the layer
boundaries of ``transform`` and the fit loops (docs/observability.md
lists them) records into the same ring whether or not the tracer is on
— like ``Trainer.input_stats`` it is what an operator always has. Each
costs two clock reads and one slotted record carrying at most the
integers the seam already holds; labels and device annotations stay
gated with the tracer.
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Any

from mmlspark_tpu.obs import context as _ctx
from mmlspark_tpu.obs import runtime as _rt
from mmlspark_tpu.obs.events import EventRecord, SpanRecord

_tls = threading.local()
_ids = itertools.count(1)  # CPython-atomic id source


class _NullSpan:
    """Shared do-nothing context for the disabled path."""

    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, *exc: Any) -> bool:
        return False


_NULL = _NullSpan()


def _annotation(name: str):
    """A jax profiler annotation, or None when jax is unavailable — the
    tracer must stay importable and usable on host-only processes."""
    try:
        from mmlspark_tpu.utils.profiling import annotate
        return annotate(name)
    except Exception:  # pragma: no cover - jax present throughout CI
        return None


class _Span:
    # rows/nbytes/minibatches are public: a boundary seam that learns a
    # count only inside the span (transform's minibatches) sets it there
    __slots__ = ("name", "cat", "labels", "links", "rows", "nbytes",
                 "minibatches", "_boundary", "_root", "_t0", "_span_id",
                 "_parent", "_depth", "_trace", "_annot")

    def __init__(self, name: str, cat: str, labels: dict | None,
                 links: tuple | None = None, boundary: bool = False,
                 rows: int | None = None, nbytes: int | None = None):
        self.name = name
        self.cat = cat
        self.labels = labels
        self.links = links
        self.rows = rows
        self.nbytes = nbytes
        self.minibatches = None
        self._boundary = boundary

    def __enter__(self) -> "_Span":
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        self._span_id = next(_ids)
        self._parent = stack[-1] if stack else None
        self._depth = len(stack)
        stack.append(self._span_id)
        self._root = None
        if self._boundary:
            # the outermost boundary span open on this thread names the
            # call every boundary span under it belongs to
            root = getattr(_tls, "root", None)
            if root is None:
                root = _tls.root = self._span_id
            self._root = root
        # the thread's active request context (obs/context.bind): spans
        # recorded while a trace is bound belong to that request
        self._trace = _ctx.current()
        self._annot = None
        if _rt._device_annotations:
            annot = _annotation(self.name)
            if annot is not None:
                annot.__enter__()
                self._annot = annot
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc: Any) -> bool:
        dur = time.perf_counter_ns() - self._t0
        if self._annot is not None:
            self._annot.__exit__(*exc)
        stack = _tls.stack
        if stack and stack[-1] == self._span_id:
            stack.pop()
        if self._root == self._span_id:
            _tls.root = None
        th = threading.current_thread()
        _rt.record(SpanRecord(self.name, self.cat, self._t0, dur,
                              th.ident or 0, th.name, self._span_id,
                              self._parent, self._depth, self.labels,
                              self._trace, self.links, self._root,
                              self.rows, self.nbytes, self.minibatches))
        return False


def span(name: str, cat: str = "host", labels: dict | None = None,
         links: tuple | None = None) -> Any:
    """Context manager tracing one interval; a shared no-op when the
    tracer is disabled (``labels``/``links`` are plain parameters, not
    ``**kwargs``, so the disabled call allocates nothing). ``links`` is
    the fan-in edge set: the trace ids of every request this span works
    for at once (obs/context.py)."""
    if not _rt._enabled:
        return _NULL
    return _Span(name, cat, labels, links)


def boundary_span(name: str, cat: str = "host", labels: dict | None = None,
                  rows: int | None = None,
                  nbytes: int | None = None) -> _Span:
    """A span of the boundary tier: recorded whether or not the tracer
    is enabled (module docstring). Only the listed layer boundaries use
    it; everything else goes through :func:`span` and stays one flag
    check when off. Call sites build ``labels`` only when the tracer is
    on, as they do for :func:`span`."""
    return _Span(name, cat, labels, None, True, rows, nbytes)


def event(name: str, cat: str = "host",
          labels: dict | None = None) -> None:
    """Record one instant event (no interval); no-op when disabled."""
    if not _rt._enabled:
        return
    th = threading.current_thread()
    _rt.record(EventRecord(name, cat, time.perf_counter_ns(),
                           th.ident or 0, th.name, labels))
