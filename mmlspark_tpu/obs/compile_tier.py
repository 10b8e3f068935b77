"""The compile tier — what building a program cost, by function, and the
collector's pauses. Always on, in the tracer's one ring, on its one clock.

JAX announces every trace (Python → jaxpr), every lowering (jaxpr → MLIR,
where a Pallas body is lowered) and every backend compile with the
function's name, and every persistent-cache hit, through
``jax.monitoring``. :func:`register` hangs ONE time-span listener and ONE
event listener there, and each of the three compile events becomes one
:class:`~mmlspark_tpu.obs.events.SpanRecord` in the ring
``boundary_span`` writes to, whether or not ``obs.enable()`` was called:

========== ==========================================================
record     what the interval is
========== ==========================================================
jit/trace   Python tracing of ``fun`` to a jaxpr. A function that calls
            jitted functions contains their traces
jit/lower   the jaxpr's conversion to an MLIR module
jit/compile XLA's backend compile OR the persistent cache's load, which
            the event wraps: ``labels["cached"]`` says which
host/gc     one pause of the cyclic collector (below)
========== ==========================================================

``labels["fun"]`` is the function's name, always (these records are
thousands a process, none a steady second); a module name such as
``jit(step)`` is unwrapped to ``step`` so that a function's trace and its
compile share a row of :func:`compile_report`. ``root_id`` /
``parent_id`` are those of the boundary span open on the thread, so a
compile under a ``transform`` root names the call it stalled; ``None``
outside one. A ``jit/trace`` record after warm-up is a retrace, and its
``fun`` and ``root_id`` say where.

**The clock.** The events carry ``time.time()`` floats. They are moved
onto ``perf_counter_ns`` through ``runtime.CLOCK_ANCHOR``, so they sit on
the clock of every other record and of ``start_epoch_ns``. A float64 of
epoch seconds resolves 2**-22 s = 238 ns this decade, and the wall clock
is slewed where the monotonic one is not (microseconds over a run): a
``jit/*`` stamp is good to about a microsecond against a boundary span's,
and two events that took under that may seem to overlap.

**What it costs.** Nothing on a steady path: JAX emits these events only
when it traces, lowers or compiles, and a call that hits the jit cache
emits none. An event costs one record, one append and two counter adds:
7-10 us. A set-up emits thousands, nearly all ``jit/trace``: jnp's own
jitted wrappers are traced wherever an outer trace first meets them
(PERF.md, PR 38: 6,104 traces in the ResNet cell's process, 1,265 of them
``add`` at 37 us each; 9,420 in the ViT train cell's), so 45-70 ms of a
set-up of 20 s and more, and a sixth of the ring.

**The collector** (``gc.callbacks``): a collection of generation 2, or
one of 1 ms and longer, leaves a ``host/gc`` record
(``labels={"generation", "collected"}``); a shorter young collection
costs the two calls and writes nothing. The callback runs wherever an
allocation lands, possibly inside ``runtime.record`` while that thread
holds the ring's lock: it never waits for a lock. The record is handed
over with one try-acquire (``runtime.try_record``); one that cannot be is
dropped and counted (:func:`gc_records_dropped`, a plain tally: the
registry's locks may not be taken there either).

Counters, always on, process totals in ``obs.registry()``: ``jit.traces``,
``jit.trace_s``, ``jit.lower_s``, ``jit.compile_s``, ``jit.cache_hits``,
``jit.cache_misses`` (compile requests the persistent cache did not
answer, those that went round it included).

obs is never the module that first imports jax (a host-only process pays
no jax import for it): ``mmlspark_tpu.obs`` registers at its import when
``jax`` is already in ``sys.modules``, else
``utils/jit_cache.place_compilation_cache`` does, which every process
that compiles for the device calls before its first compile.
"""

from __future__ import annotations

import gc
import re
import sys
import threading
import time

from mmlspark_tpu.obs import runtime as _rt
from mmlspark_tpu.obs import spans as _spans
from mmlspark_tpu.obs.events import SpanRecord
from mmlspark_tpu.obs.metrics import registry as _registry

TRACE, LOWER, COMPILE, GC = "jit/trace", "jit/lower", "jit/compile", "host/gc"
JIT_NAMES = (TRACE, LOWER, COMPILE)

# jax.monitoring's event -> (record name, seconds counter)
_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": (TRACE, "jit.trace_s"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        (LOWER, "jit.lower_s"),
    "/jax/core/compile/backend_compile_duration":
        (COMPILE, "jit.compile_s"),
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_MODULE_NAME = re.compile(r"\w+\((.*)\)")

#: a young collection shorter than this writes no record
GC_MIN_NS = 1_000_000

# per thread: `cache_hit` (set by the hit event, consumed by the compile
# record that closes next on the thread) and `compiles` (count, summed ns
# of the thread's jit/compile records: what note_dispatch takes a delta of)
_tls = threading.local()
_registered = False
_register_lock = threading.Lock()
_gc_t0 = 0
_gc_dropped = 0


def _write(name: str, cat: str, start_ns: int, dur_ns: int, labels: dict,
           hand_over) -> bool:
    """One record of this tier under the boundary span open on the
    thread. Takes no lock itself (the collector's callback comes through
    here): ``threading.current_thread()`` would, for a thread Python did
    not start."""
    tid = threading.get_ident()
    thread = threading._active.get(tid)
    stack = getattr(_spans._tls, "stack", None)
    return hand_over(SpanRecord(
        name, cat, start_ns, dur_ns, tid,
        thread.name if thread is not None else f"thread-{tid}",
        next(_spans._ids), stack[-1] if stack else None,
        len(stack) if stack else 0, labels, None, None,
        getattr(_spans._tls, "root", None)))


def _on_event(event: str, **kwargs) -> None:
    if event == _CACHE_HIT:
        _tls.cache_hit = True


def _on_time_span(event: str, start_time: float, end_time: float,
                  **kwargs) -> None:
    kind = _EVENTS.get(event)
    if kind is None:
        return
    name, seconds = kind
    try:
        fun = str(kwargs.get("fun_name", ""))
        module = _MODULE_NAME.fullmatch(fun)
        labels = {"fun": module.group(1) if module else fun}
        dur_ns = max(round((end_time - start_time) * 1e9), 0)
        reg = _registry()
        if name == COMPILE:
            cached = _tls.__dict__.pop("cache_hit", False)
            labels["cached"] = cached
            reg.counter("jit.cache_hits" if cached
                        else "jit.cache_misses").add()
            n, ns = getattr(_tls, "compiles", (0, 0))
            _tls.compiles = (n + 1, ns + dur_ns)
        elif name == TRACE:
            reg.counter("jit.traces").add()
        reg.counter(seconds).add(dur_ns / 1e9)
        _write(name, "jit", _rt.from_epoch_ns(round(start_time * 1e9)),
               dur_ns, labels, _rt.record)
    except Exception:  # pragma: no cover - JAX calls this inside the
        pass           # user's compile: telemetry never breaks it


def _on_gc(phase: str, info: dict) -> None:
    global _gc_t0, _gc_dropped
    if phase == "start":
        _gc_t0 = time.perf_counter_ns()
        return
    t0, _gc_t0 = _gc_t0, 0
    dur_ns = time.perf_counter_ns() - t0
    if not t0 or (info["generation"] < 2 and dur_ns < GC_MIN_NS):
        return
    if not _write(GC, "host", t0, dur_ns,
                  {"generation": info["generation"],
                   "collected": info["collected"]}, _rt.try_record):
        _gc_dropped += 1


def register() -> bool:
    """Hang the collector's callback and, once ``jax`` is imported, the
    two ``jax.monitoring`` listeners. A second call is a no-op; ``True``
    when the listeners are in."""
    global _registered
    if _registered:
        return True
    with _register_lock:
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)
        if not _registered and "jax" in sys.modules:
            import jax.monitoring as monitoring

            monitoring.register_event_time_span_listener(_on_time_span)
            monitoring.register_event_listener(_on_event)
            _registered = True
    return _registered


def gc_records_dropped() -> int:
    """``host/gc`` records the collector's callback could not hand over
    because the ring's lock was taken, since the process began."""
    return _gc_dropped


def thread_compiles() -> tuple[int, int]:
    """``(jit/compile records, their summed nanoseconds)`` this thread
    has written so far. ``obs.device.note_dispatch`` takes the difference
    around a call: whether the dispatch compiled, and for how long."""
    register()
    return getattr(_tls, "compiles", (0, 0))


def tier_records(records: list | None = None) -> list:
    """The ``jit/*`` and ``host/gc`` records among ``records`` (default:
    the ring), in the order they were written."""
    if records is None:
        records = _rt.spans()
    return [r for r in records if isinstance(r, SpanRecord)
            and (r.name in JIT_NAMES or r.name == GC)]


def union_seconds(records: list, names: tuple = JIT_NAMES) -> float:
    """Seconds the records named in ``names`` cover together, an instant
    counted once however many of them hold it: the trace of ``f`` holds
    the traces of the jitted functions ``f`` calls, so a plain sum of
    ``jit/trace`` durations counts those twice."""
    from mmlspark_tpu.obs.device import _measure, _union

    return _measure(_union([(r.start_ns, r.end_ns) for r in records
                            if r.name in names])) / 1e9


def compile_report(records: list | None = None) -> list[dict]:
    """What building programs cost, by function: rows of ``fun``,
    ``traces``, ``trace_s``, ``lower_s``, ``compiles``, ``compile_s`` and
    ``cached`` (of ``compiles``, those the persistent cache answered),
    largest ``trace_s + lower_s + compile_s`` first, over ``records``
    (default: the ring).

    Each row sums its function's OWN intervals, and an outer trace holds
    the inner ones it set off: the rows of ``f`` and of a jitted ``g``
    that ``f`` calls both count the time ``g`` was traced under ``f``. So
    rows rank functions; they do not add up. A total is a union
    (:func:`union_seconds`), as in ``host_phase_split``."""
    rows: dict[str, dict] = {}
    for r in tier_records(records):
        if r.name == GC:
            continue
        fun = (r.labels or {}).get("fun", "")
        row = rows.get(fun)
        if row is None:
            row = rows[fun] = {"fun": fun, "traces": 0, "trace_s": 0.0,
                               "lower_s": 0.0, "compiles": 0,
                               "compile_s": 0.0, "cached": 0}
        seconds = r.dur_ns / 1e9
        if r.name == TRACE:
            row["traces"] += 1
            row["trace_s"] += seconds
        elif r.name == LOWER:
            row["lower_s"] += seconds
        else:
            row["compiles"] += 1
            row["compile_s"] += seconds
            row["cached"] += bool(r.labels.get("cached"))
    return sorted(rows.values(), key=lambda row: -(
        row["trace_s"] + row["lower_s"] + row["compile_s"]))
