"""Obs runtime state: the enable flag, the span ring buffer, and the jit
compile-cache hook.

The tracer is OFF by default. Every instrumented seam (plan crossings,
train input, serve dispatch, decode pools) guards itself with one read of
this module's ``_enabled`` flag, so production paths that never enable
observability pay a single attribute load + branch per seam — no
allocation, no lock (the ``< 2%`` disabled-overhead gate in
``tools/perf_smoke.py:check_obs_overhead``).

Enable programmatically (``obs.enable()``), or from the environment with
``MMLSPARK_TPU_OBS=1`` (read once at import through ``core.config``).
The boundary tier (``obs/spans.boundary_span``) and the compile tier
(``obs/compile_tier.py``: JAX's own trace / lower / compile events and the
collector's pauses) record into the same ring regardless of the flag.

Spans are stamped with ``time.perf_counter_ns``; ONE anchor pair taken
here at import (:data:`CLOCK_ANCHOR`) places them on the Unix epoch
(:func:`to_epoch_ns`). A ``jax.profiler`` trace stamps its planes
relative to the session and the session's start on that same epoch (the
``profile_start_time`` stat of its ``Task Environment`` plane), so the two
can be laid side by side (PERF.md has the offset measured on the chip).

The **compile-cache hook** lives here too: reading an XLA program count
off a jitted callable's own compile cache was serve-local in PR 4
(``DynamicBatcher.compiled_programs``); it is the process-wide recompile
observable every layer wants, so :func:`jit_cache_size` /
:func:`compiled_programs` are owned by obs and the serve layer delegates.
"""

from __future__ import annotations

import itertools
import os
import threading
import time
from collections import deque
from typing import Any

from mmlspark_tpu.core import config
from mmlspark_tpu.obs.events import EventRecord, SpanRecord
from mmlspark_tpu.obs.lockwitness import named_lock


def _clock_anchor() -> tuple[int, int]:
    """``(perf_counter_ns, time_ns)`` of one instant: the epoch read sits
    between two monotonic reads and is paired with their midpoint."""
    p0 = time.perf_counter_ns()
    wall = time.time_ns()
    return (p0 + time.perf_counter_ns()) // 2, wall


# the process's one perf→epoch anchor; a span's epoch time is its
# perf_counter stamp shifted by the pair's difference. On Linux both
# clocks follow the same NTP slew and part only when the wall clock is
# stepped, so the pair holds to microseconds over a run (PERF.md: 2-5 us
# on the chip machine); after a step every later epoch stamp is off by it
CLOCK_ANCHOR = _clock_anchor()


def to_epoch_ns(perf_ns: int) -> int:
    """A ``time.perf_counter_ns`` stamp of this process in Unix-epoch
    nanoseconds."""
    return perf_ns - CLOCK_ANCHOR[0] + CLOCK_ANCHOR[1]


def from_epoch_ns(epoch_ns: int) -> int:
    """The inverse of :func:`to_epoch_ns`: an instant somebody else read
    off the Unix epoch (``time.time``), as a ``perf_counter_ns`` stamp of
    this process, through the same one anchor."""
    return epoch_ns - CLOCK_ANCHOR[1] + CLOCK_ANCHOR[0]


def process_start_epoch_ns() -> int | None:
    """When the kernel started this process, in Unix-epoch nanoseconds:
    the zero "ready after N seconds" is counted from, before the
    interpreter, before any import. ``starttime`` of ``/proc/self/stat``
    (clock ticks since boot, so 10 ms of resolution) on the boot time
    (``time_ns`` less ``CLOCK_BOOTTIME``, read now). ``None`` where there
    is no ``/proc`` or no such clock."""
    try:
        with open("/proc/self/stat", encoding="ascii") as fh:
            # the command name may hold spaces and parentheses: the
            # numbered fields resume after its closing one (state is 3)
            fields = fh.read().rpartition(")")[2].split()
        ticks = int(fields[22 - 3])
        boot_ns = time.time_ns() - time.clock_gettime_ns(
            time.CLOCK_BOOTTIME)
        return boot_ns + ticks * 1_000_000_000 // os.sysconf("SC_CLK_TCK")
    except (OSError, AttributeError, ValueError, IndexError):
        return None


DEFAULT_BUFFER = 65536
# distinct request traces retained for grouping (obs/context.py) before
# drop-oldest eviction kicks in — see note_traces below
DEFAULT_MAX_TRACES = 4096

# single module-level flag instrumented seams check; mutate only through
# enable()/disable()
_enabled = False
# when True, spans additionally enter jax.profiler.TraceAnnotation so an
# XProf/Perfetto capture interleaves host spans with the device timeline
_device_annotations = False
# bounded ring buffer of completed SpanRecord/EventRecord (oldest evicted)
_buffer: deque = deque(maxlen=DEFAULT_BUFFER)
_lock = named_lock("obs.runtime._lock")
# total records ever appended — lets the trace evictor compute how many
# records arrived while it filtered outside the lock (len() can't: a
# full ring stays at maxlen while still receiving appends)
_append_seq = 0
# one physical span-eviction at a time; a thread that loses the race
# skips — the live-set filter already bounds what readers group, the
# next eviction round reclaims the spans
_evict_lock = named_lock("obs.runtime._evict_lock")

# ---- trace retention (the request_traces eviction policy) ----
# The span ring is bounded by record COUNT, which bounded nothing per
# TRACE: a sustained request burst filled the ring with thousands of
# completed traces that request_traces() kept grouping (and the export
# kept rendering as flows) until someone called clear(). Retention is
# now explicit: the first `_max_traces` distinct trace ids stay live;
# beyond that the OLDEST traces are dropped in batches — their spans
# evicted from the ring, the drop counted in `obs.traces_dropped` — so
# a server left tracing for days holds a bounded, recent trace set.
_max_traces = DEFAULT_MAX_TRACES
_trace_order: dict[int, None] = {}  # insertion-ordered live trace ids
# recently dropped ids (bounded): a dropped trace whose in-flight spans
# complete later must NOT be resurrected as the "newest" trace — that
# would group a tail-only partial trace and double-count the drop
_dropped_ids: dict[int, None] = {}
_trace_lock = named_lock("obs.runtime._trace_lock")
_traces_dropped = 0


def enable(buffer_size: int = DEFAULT_BUFFER,
           device_annotations: bool = False,
           device: bool | None = None,
           max_traces: int | None = None) -> None:
    """Turn the tracer on. Idempotent; a changed ``buffer_size`` rebuilds
    the ring buffer (keeping the newest records that fit).

    ``device=True`` additionally enables the device-attribution pillar
    (:mod:`mmlspark_tpu.obs.device`: compile-time histograms,
    ``plan.segment.*`` cost/memory gauges, live memory polling) and
    implies ``device_annotations``; ``device=False`` switches it off.
    Omitted kwargs restore their DEFAULTS, not the previous call's
    values — and the default for ``device`` is the environment baseline
    (``MMLSPARK_TPU_OBS_DEVICE``), so a library's plain ``enable()``
    (e.g. ``tools/serve.py --obs``) never silently defeats the
    documented no-code-changes env path. ``max_traces`` re-bounds the
    live request-trace retention (drop-oldest); omitting it restores
    the default bound, same as ``buffer_size`` restores the default
    ring."""
    global _enabled, _device_annotations, _buffer, _max_traces
    dev = (bool(config.get("obs_device", False)) if device is None
           else bool(device))
    with _lock:
        if _buffer.maxlen != buffer_size:
            _buffer = deque(_buffer, maxlen=int(buffer_size))
        _device_annotations = bool(device_annotations) or dev
        _max_traces = (DEFAULT_MAX_TRACES if max_traces is None
                       else max(int(max_traces), 1))
        _enabled = True
    from mmlspark_tpu.obs import device as _device_mod
    if dev:
        _device_mod.enable()
    else:
        _device_mod.disable()


def disable() -> None:
    """Turn the tracer off (records already captured stay readable).
    The device-attribution pillar rides the tracer: it is switched off
    here too (re-enable with ``enable(device=True)``)."""
    global _enabled
    with _lock:
        _enabled = False
    from mmlspark_tpu.obs import device as _device_mod
    _device_mod.disable()


def enabled() -> bool:
    return _enabled


def clear() -> None:
    """Drop captured spans/events, the live-trace retention set, and
    the dropped-trace tally (metrics live in obs.metrics; clear those
    via ``obs.registry().reset()``)."""
    global _traces_dropped
    with _lock:
        _buffer.clear()
    with _trace_lock:
        _trace_order.clear()
        _dropped_ids.clear()
        _traces_dropped = 0


def record(item: SpanRecord | EventRecord) -> None:
    """Append one finished record. The append takes ``_lock`` so it
    serializes against the trace-eviction ring rebuild in
    :func:`note_traces` — a lock-free append could land on the ring
    object being swapped out and silently vanish. (Uncontended acquire
    is ~100 ns on a path that already allocates a record; the disabled
    path never reaches here.) Records carrying request trace ids also
    register in the live-trace set, which enforces the drop-oldest
    retention bound."""
    global _append_seq
    with _lock:
        _buffer.append(item)
        _append_seq += 1
    trace = getattr(item, "trace", None)
    links = getattr(item, "links", None)
    if trace is not None or links:
        note_traces(trace, links)


def try_record(item: SpanRecord) -> bool:
    """:func:`record` for a caller that may not wait: the collector's
    callback (``obs/compile_tier.py``) runs wherever an allocation lands,
    possibly on a thread that is inside :func:`record` and holds
    ``_lock``, which is not re-entrant. One try-acquire; ``False`` when
    the lock is taken and the record was not written. For records
    outside any request trace only."""
    global _append_seq
    if not _lock.acquire(blocking=False):
        return False
    try:
        _buffer.append(item)
        _append_seq += 1
    finally:
        _lock.release()
    return True


def note_traces(trace: int | None, links: tuple | None) -> None:
    """Register a record's trace ids as live; evict the oldest traces
    (batched — each eviction rebuilds the ring once) past the bound."""
    global _traces_dropped, _buffer
    with _trace_lock:
        if trace is not None and trace not in _dropped_ids:
            _trace_order.setdefault(trace, None)
        for t in links or ():
            if t not in _dropped_ids:
                _trace_order.setdefault(t, None)
        excess = len(_trace_order) - _max_traces
        if excess <= 0:
            return
        # drop in batches of at least max_traces/8 so the O(ring) span
        # eviction amortizes over many new traces, not one rebuild each
        n_drop = max(excess, _max_traces // 8, 1)
        it = iter(_trace_order)
        dropped = {next(it) for _ in range(min(n_drop,
                                               len(_trace_order)))}
        for t in dropped:
            del _trace_order[t]
            _dropped_ids[t] = None
        _traces_dropped += len(dropped)
        # the resurrection guard is itself bounded: only an id dropped
        # while its request was STILL IN FLIGHT can come back, so
        # remembering the most recent max(max_traces, 1024) drops is
        # plenty (the floor keeps the guard meaningful under a tiny
        # test-sized max_traces; the cost is a few thousand ints)
        cap = max(_max_traces, 1024)
        while len(_dropped_ids) > cap:
            del _dropped_ids[next(iter(_dropped_ids))]
        # filter against the ACCUMULATED dropped memo, not just this
        # round's batch: a round that loses the evict race below skips
        # its rebuild, and only the memo lets a later round reclaim
        # those spans too
        dropped_all = set(_dropped_ids)

    def keep(r) -> bool:
        tr = getattr(r, "trace", None)
        ln = getattr(r, "links", None)
        if tr is None and not ln:
            return True  # non-request records are never trace-evicted
        if tr is not None and tr not in dropped_all:
            return True
        return any(t not in dropped_all for t in ln or ())

    # physically evict the dropped traces' spans — but run the O(ring)
    # Python filter OUTSIDE the record lock: under sustained serve
    # traffic this fires every max_traces/8 new traces, and holding
    # _lock for a 65536-record pass would stall every lane's span
    # completion for milliseconds. The locked sections are two C-level
    # list() copies plus the (small) tail that arrived mid-filter; a
    # concurrent evictor skips — readers already filter by the live
    # set, so deferred spans are invisible until the next round.
    if _evict_lock.acquire(blocking=False):
        try:
            with _lock:
                snapshot = list(_buffer)
                seq0 = _append_seq
            kept = [r for r in snapshot if keep(r)]
            with _lock:
                n_new = min(_append_seq - seq0, len(_buffer))
                tail = list(_buffer)[len(_buffer) - n_new:]
                _buffer = deque(
                    kept + [r for r in tail if keep(r)],
                    maxlen=_buffer.maxlen)
        finally:
            _evict_lock.release()
    from mmlspark_tpu.obs.metrics import registry as _reg
    _reg().counter("obs.traces_dropped").add(len(dropped))


def live_traces() -> set:
    """The trace ids currently retained for grouping (newest
    ``max_traces`` distinct ids seen by the ring)."""
    with _trace_lock:
        return set(_trace_order)


def dropped_trace_count() -> int:
    """Total traces evicted by the retention policy since the last
    :func:`clear`. Mirrors the ``obs.traces_dropped`` registry counter
    when tracer and registry are reset together (``obs.clear()`` +
    ``obs.registry().reset()``, as the test fixtures do); the two
    diverge if only one side is reset."""
    return _traces_dropped


def spans() -> list:
    """Snapshot of captured records, oldest first. (``list(deque)`` is a
    single atomic C call — safe against concurrent ``record()``; a plain
    comprehension over the live deque would raise ``RuntimeError`` when
    another thread appends mid-iteration.)"""
    return list(_buffer)


def captured_count() -> int:
    """O(1) record count (no buffer copy — the /metrics poll path)."""
    return len(_buffer)


def ring_full() -> bool:
    """The ring is at its bound: older records may have been evicted, so
    a sum over "every record since the process began" is no longer one."""
    return len(_buffer) == _buffer.maxlen


def span_records() -> list[SpanRecord]:
    return [r for r in spans() if isinstance(r, SpanRecord)]


# ---- request trace ids (obs/context.py) ----

# process-wide monotonic trace-id source: itertools.count.__next__ is a
# single CPython bytecode step, so ids are unique without a lock even
# when every HTTP handler thread mints at once
_trace_ids = itertools.count(1)


def next_trace_id() -> int:
    """A fresh, process-unique request trace id (never reused; surviving
    ``clear()`` on purpose — a cleared buffer must not let a new request
    collide with ids already serialized into an exported trace)."""
    return next(_trace_ids)


# ---- the jit compile-cache hook (promoted from serve/batcher.py) ----

def jit_cache_size(jitted: Any) -> int | None:
    """XLA executables in one jitted callable's compile cache; ``None``
    when the jit object doesn't expose it (older jax)."""
    size_of = getattr(jitted, "_cache_size", None)
    if size_of is None:
        return None
    return int(size_of())


def compiled_programs(cache_host: Any) -> int | None:
    """Total XLA executables across ``cache_host``'s compiled-segment
    cache (``core.plan._cached_segment``'s store) — the recompile
    observable behind the serve bucket-ladder gate and ``tools/trace.py``.
    ``None`` when any cached jit doesn't expose its cache size; ``0`` for
    a host that never compiled a segment."""
    host_dict = getattr(cache_host, "__dict__", {})
    store = host_dict.get("_plan_cache")
    if not store:
        return 0
    # snapshot under the plan lock: dispatch threads insert/evict entries
    # concurrently, and iterating a mutating dict raises
    lock = host_dict.get("_plan_lock")
    if lock is not None:
        with lock:
            entries = list(store.values())
    else:  # pragma: no cover - cache always created with its lock
        entries = list(store.values())
    total = 0
    for _tokens, compiled, _pinned in entries:
        size = jit_cache_size(compiled[0])
        if size is None:
            return None
        total += size
    return total


# honor MMLSPARK_TPU_OBS=1 (or config.set("obs", True) before first
# import) — the env-var path for tracing a production run without code.
# MMLSPARK_TPU_OBS_DEVICE=1 additionally turns on the device-attribution
# pillar (+ jax.profiler annotations); it implies the tracer. Explicit
# obs.enable(...) kwargs later override both (the env is read ONCE here)
if config.get("obs", False) \
        or config.get("obs_device", False):  # pragma: no cover - env
    enable(device=bool(config.get("obs_device", False)))
