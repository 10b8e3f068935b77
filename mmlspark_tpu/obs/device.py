"""Device attribution — compiled-program cost, memory accounting, and the
host's phase split.

PR 5 made the crossing *counts* observable (every H2D/D2H through the
plan seams lands in the registry), but the device itself stayed dark:
what did each compiled segment cost to build, how much HBM does it
touch, and which phase of the host's work fills a call's wall clock?
This module is that accounting, in four pieces, the first three
recorded through the shared registry (the one-substrate rule):

* **compile attribution** (:func:`note_dispatch`) — the plan dispatch
  seam calls it after every program invocation when the pillar is on.
  Whether the call compiled, and for how long, is what the compile tier
  (``obs/compile_tier.py``: JAX's own compile events, always recorded)
  wrote for the calling thread inside the call: its ``jit/compile``
  records become one ``plan.compile_ms{segment=…}`` histogram
  observation plus a ``plan.xla_compiles{segment=…}`` count. That is
  compile (or the persistent cache's load) alone: not the trace, the
  lowering or the dispatch.
* **cost/memory capture** (:func:`_capture_cost`) — once per
  ``(program, entry shape)`` the same program is AOT-lowered and
  compiled so XLA's own ``cost_analysis``/``memory_analysis`` can be
  read (the dispatch cache's executable is not introspectable, so this
  is a second compile of an identical program — the documented price of
  the opt-in pillar; the plan seam calls it *outside* the
  ``plan/dispatch`` span so the recompile is unspanned time in the
  split, never dispatch), populating ``plan.segment.flops``,
  ``plan.segment.bytes`` and ``plan.segment.peak_hbm`` gauges keyed by
  ``{segment=…, shape=…}``. ``peak_hbm`` prefers the backend's
  ``memory_analysis`` (argument + output + temp buffers); backends that
  do not report it (the CPU dryrun mesh) fall back to the cost model's
  ``bytes accessed`` so the gauge is always populated.
* **live memory** (:func:`poll_memory`) — ``device.memory_stats()``
  where the backend exposes it (TPU/GPU), published as
  ``device.mem_bytes_in_use{device=…}`` / ``device.mem_peak_bytes`` /
  ``device.mem_limit_bytes`` gauges; dryrun/CPU devices return nothing
  and the poll is a cheap no-op (never an error, never a jax init).
* **host phase split** (:func:`host_phase_split`) — where the HOST's
  time went, from the boundary-tier spans (``obs/spans.boundary_span``,
  always recorded): coerce, upload, dispatch self time, the blocking
  fetch that waits for the device, output assembly, the train loop's
  step dispatch and lagged loss fetch, and the unspanned rest. Dispatch
  is asynchronous, so none of these is device time: what the device did
  comes from a profiler trace (``benchmark/trace_reduce.py``), and the
  spans' epoch stamps (``SpanRecord.start_epoch_ns``) lay the two side
  by side.

The attribution pillar (the first three pieces' recording side) is OFF
by default and independent of the tracer flag; the split only reads:
``obs.enable(device=True)`` (or ``MMLSPARK_TPU_OBS_DEVICE=1``) turns it
on along with ``jax.profiler`` device annotations. Disabled, the plan
seam pays one extra attribute check per dispatched minibatch — inside
the < 2% ``check_obs_overhead`` budget.
"""

from __future__ import annotations

import sys
import threading
import weakref
from typing import Any

from mmlspark_tpu.obs import runtime as _rt
from mmlspark_tpu.obs.compile_tier import (  # noqa: F401 - the plan seam's
    GC, JIT_NAMES, thread_compiles,
)
from mmlspark_tpu.obs.metrics import registry as _registry

# the device-attribution pillar flag — mutate only through
# enable()/disable() (obs.runtime.enable(device=True) routes here)
_enabled = False


def enable() -> None:
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def enabled() -> bool:
    return _enabled


# per-jitted-program set of entry shapes already attributed. WeakKey so a
# segment evicted from the plan cache releases its memo with it
_seen: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_seen_lock = threading.Lock()


def reset() -> None:
    """Drop the per-program attribution memos (test isolation)."""
    with _seen_lock:
        _seen.clear()


def note_dispatch(fn: Any, dev_params: Any, chunk: Any,
                  label: str | None, compiled_before: tuple) -> None:
    """Attribute one program invocation at the plan dispatch seam.

    ``compiled_before`` is :func:`thread_compiles` read before the call;
    what the compile tier has written for this thread since is the
    call's XLA compiles (or cache loads) and their time. Attribution
    must never break dispatch — any failure here is swallowed."""
    try:
        shape = tuple(getattr(chunk, "shape", ()))
        compiles, compile_ns = thread_compiles()
        compiles -= compiled_before[0]
        with _seen_lock:
            shapes = _seen.get(fn)
            if shapes is None:
                shapes = _seen[fn] = set()
            first = shape not in shapes
            shapes.add(shape)
        if not (compiles or first):
            return
        seg = label or "segment"
        reg = _registry()
        if compiles:
            reg.counter("plan.xla_compiles", segment=seg).add(compiles)
            reg.histogram("plan.compile_ms", segment=seg).observe(
                (compile_ns - compiled_before[1]) / 1e6)
        if first:
            # cost capture keys on the per-process memo, not on whether
            # this call compiled: a program compiled before the pillar
            # was enabled (bench warms, then traces) still gets its
            # cost/memory gauges — only the compile TIME is unknowable
            # then
            _capture_cost(fn, dev_params, chunk, seg, shape, reg)
    except Exception:  # pragma: no cover - attribution is best-effort
        pass


def _capture_cost(fn: Any, dev_params: Any, chunk: Any, seg: str,
                  shape: tuple, reg: Any) -> None:
    """AOT-compile ``fn`` at this entry shape and publish XLA's cost and
    memory analyses as ``plan.segment.*`` gauges."""
    import jax

    sds = jax.ShapeDtypeStruct(tuple(chunk.shape), chunk.dtype)
    compiled = fn.lower(dev_params, sds).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    cost = cost or {}
    lbl = {"segment": seg, "shape": str(shape)}
    flops = cost.get("flops")
    if flops is not None:
        reg.gauge("plan.segment.flops", **lbl).set(float(flops))
    nbytes = cost.get("bytes accessed")
    if nbytes is not None:
        reg.gauge("plan.segment.bytes", **lbl).set(float(nbytes))
    peak = None
    try:
        mem = compiled.memory_analysis()
    except Exception:
        mem = None
    if mem is not None:
        try:
            peak = float(mem.argument_size_in_bytes
                         + mem.output_size_in_bytes
                         + mem.temp_size_in_bytes)
        except Exception:
            peak = None
    if peak is None:
        # dryrun-safe fallback: the cost model's total bytes touched is
        # the best available stand-in, so the gauge is always populated
        peak = float(nbytes) if nbytes is not None else 0.0
    reg.gauge("plan.segment.peak_hbm", **lbl).set(peak)


def poll_memory(reg: Any = None) -> dict:
    """Publish live/peak device-memory gauges from ``memory_stats()``.

    Returns ``{device_key: stats}`` for devices that report; empty on
    backends without memory stats (the CPU dryrun mesh) and when jax was
    never imported (polling must not initialize a backend — the flight
    watchdog calls this from its own thread)."""
    if "jax" not in sys.modules:
        return {}
    import jax

    # "jax imported" is NOT "backend initialized": jax.local_devices()
    # would INITIALIZE the default backend — fatal for an app that
    # imports jax early but calls jax.distributed.initialize() later
    # (the poll would lock it into single-process mode / grab HBM).
    # Poll only once the app itself has brought a backend up.
    from jax._src import xla_bridge as _xb
    if not _xb.backends_are_initialized():
        return {}

    reg = reg if reg is not None else _registry()
    out: dict = {}
    try:
        devices = jax.local_devices()
    except Exception:  # pragma: no cover - backend not initialized
        return {}
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        key = f"{d.platform}:{getattr(d, 'id', 0)}"
        used = stats.get("bytes_in_use")
        peak = stats.get("peak_bytes_in_use")
        limit = stats.get("bytes_limit")
        if used is not None:
            reg.gauge("device.mem_bytes_in_use", device=key).set(used)
        if peak is not None:
            reg.gauge("device.mem_peak_bytes", device=key).set(peak)
        if limit is not None:
            reg.gauge("device.mem_limit_bytes", device=key).set(limit)
        out[key] = {"bytes_in_use": used, "peak_bytes_in_use": peak,
                    "bytes_limit": limit}
    return out


# the host phases, in order of precedence where spans of different
# threads overlap: an instant belongs to the first phase that covers it.
# All are boundary-tier span names (docs/observability.md)
_PHASES = (
    # the two stalls that interrupt whatever span they land in (the
    # compile tier's records): a collector pause inside plan/d2h is a
    # pause, not fetch wait; a compile inside plan/dispatch is a
    # compile, not dispatch. Both 0 on a steady window
    ("gc", (GC,)),
    ("jit", JIT_NAMES),
    ("h2d", ("plan/h2d",)),
    ("dispatch", ("plan/dispatch",)),       # self time: h2d taken out
    ("fetch_wait", ("plan/d2h",)),          # the wait for the device
    ("coerce", ("transform/coerce",)),
    ("assemble", ("transform/assemble",)),
    ("loss_fetch", ("train/loss_fetch",)),  # the train loop's wait
    ("step_dispatch", ("train/step",)),
)
# spans that only bound the wall (their self time is unspanned)
_ROOTS = ("transform",)
_PHASE_OF = {name: phase for phase, names in _PHASES for name in names}
# phases that only claim time inside the wall the boundary spans bound
_INTERRUPTS = ("gc", "jit")


def _union(intervals: list) -> list:
    """Merge ``(start, end)`` intervals into a disjoint, sorted union."""
    if not intervals:
        return []
    intervals = sorted(intervals)
    out = [list(intervals[0])]
    for s, e in intervals[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _measure(intervals: list) -> float:
    return float(sum(e - s for s, e in intervals))


def _subtract(base: list, cut: list) -> list:
    """``base`` minus ``cut``, both disjoint sorted unions."""
    out = []
    for s, e in base:
        for cs, ce in cut:
            if ce <= s or cs >= e:
                continue
            if cs > s:
                out.append((s, cs))
            s = max(s, min(ce, e))
            if s >= e:
                break
        if s < e:
            out.append((s, e))
    return out


def host_phase_split(records: list | None = None,
                     wall_s: float | None = None) -> dict | None:
    """Where the host's time went, from the boundary spans of a run.

    Attribution is over the UNION of span intervals — concurrent serve
    lanes (dp>1) emit overlapping ``plan/dispatch`` spans, and a naive
    per-span duration sum would report more than the wall. Where spans
    of different phases overlap the earlier entry of ``_PHASES`` wins:
    a collector pause (``host/gc``) is ``gc`` and a trace, lowering or
    compile (``jit/*``) is ``jit`` wherever they land, inside the wall
    the boundary spans bound (neither stretches it; both are 0 on a
    steady window), ``plan/h2d`` is ``h2d``, ``plan/dispatch`` time outside its nested
    h2d is ``dispatch`` (issuing the async call and the fetch),
    ``plan/d2h`` outside both is ``fetch_wait`` (the host blocked until
    the device produced a minibatch), and so on down the table. A
    single-threaded capture decomposes exactly as a per-span sum would.

    Returns ``<phase>_s`` (seconds) and ``<phase>_share`` (of the wall)
    for every phase and for ``unspanned``, the wall no phase covers: a
    ``transform`` root's self time and whatever the caller did between
    calls. The wall is ``wall_s`` when given (a benchmark's window, which
    begins before the first span and ends after the last), else the
    covered wall from the first span's start to the last span's end.
    ``None`` when ``records`` (default: the ring) hold no such span.
    This is HOST time: dispatch is asynchronous, so no field says what
    the device was doing."""
    from mmlspark_tpu.obs.events import SpanRecord

    by_phase: dict[str, list] = {phase: [] for phase, _ in _PHASES}
    lo = hi = None
    if records is None:
        records = _rt.spans()
    for r in records:
        if not isinstance(r, SpanRecord):
            continue
        phase = _PHASE_OF.get(r.name)
        if phase is None and r.name not in _ROOTS:
            continue
        if phase is not None:
            by_phase[phase].append((r.start_ns, r.end_ns))
            if phase in _INTERRUPTS:
                continue
        lo = r.start_ns if lo is None else min(lo, r.start_ns)
        hi = r.end_ns if hi is None else max(hi, r.end_ns)
    if lo is None:
        return None
    for phase in _INTERRUPTS:
        by_phase[phase] = [(max(s, lo), min(e, hi))
                           for s, e in by_phase[phase] if e > lo and s < hi]
    wall = (hi - lo) / 1e9 if wall_s is None else float(wall_s)
    out = {"wall_s": wall}
    claimed: list = []
    for phase, _names in _PHASES:
        own = _subtract(_union(by_phase[phase]), claimed)
        out[f"{phase}_s"] = _measure(own) / 1e9
        claimed = _union(claimed + own)
    out["unspanned_s"] = max(wall - _measure(claimed) / 1e9, 0.0)
    if wall > 0:
        for key in [k for k in out if k != "wall_s"]:
            out[key[:-2] + "_share"] = out[key] / wall
    return out
