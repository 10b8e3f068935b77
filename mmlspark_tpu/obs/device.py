"""Device attribution — compiled-program cost, memory accounting, and the
compute/transfer/idle split.

PR 5 made the crossing *counts* observable (every H2D/D2H through the
plan seams lands in the registry), but the device itself stayed dark:
what did each compiled segment cost to build, how much HBM does it
touch, and how much of a step's wall clock is compute versus transfer
versus host idle? This module is that accounting, in three pieces, all
recorded through the shared registry (the one-substrate rule):

* **compile attribution** (:func:`note_dispatch`) — the plan dispatch
  seam calls it after every program invocation when the pillar is on.
  A fresh XLA compile is detected by compile-cache growth (the obs-owned
  ``jit_cache_size`` hook, extended from a lifetime count to a
  per-dispatch delta; first-seen-shape memo when the jit object hides
  its cache), and attributed as one ``plan.compile_ms{segment=…}``
  histogram observation plus a ``plan.xla_compiles{segment=…}`` count —
  compile-time histograms keyed by segment and entry bucket.
* **cost/memory capture** (:func:`_capture_cost`) — once per
  ``(program, entry shape)`` the same program is AOT-lowered and
  compiled so XLA's own ``cost_analysis``/``memory_analysis`` can be
  read (the dispatch cache's executable is not introspectable, so this
  is a second compile of an identical program — the documented price of
  the opt-in pillar; the plan seam calls it *outside* the
  ``plan/dispatch`` span so the recompile lands in the split's idle
  time, never its compute), populating ``plan.segment.flops``,
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
* **timeline split** (:func:`device_time_split`) — the honest
  compute/transfer/idle decomposition of a captured run, derived from
  the *existing* ``plan/dispatch``/``plan/h2d``/``plan/d2h`` spans (no
  new seams): dispatch intervals minus their nested H2D time are
  compute-issue, D2H drains are transfer, and whatever the wall clock
  holds beyond both is host idle. This is what ``bench.py`` reports
  next to rows/s, so "input-bound" claims are backed by attribution.

The pillar is OFF by default and independent of the tracer flag:
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
                  label: str | None, cache_before: int | None,
                  dur_s: float) -> None:
    """Attribute one program invocation at the plan dispatch seam.

    ``cache_before`` is ``jit_cache_size(fn)`` read before the call;
    growth afterwards means the call included an XLA compile and its
    duration is the compile time (dispatch issue is sub-ms next to any
    real compile). Jit objects without a readable cache fall back to a
    first-seen-shape memo. Attribution must never break dispatch — any
    failure here is swallowed."""
    try:
        shape = tuple(getattr(chunk, "shape", ()))
        after = _rt.jit_cache_size(fn)
        with _seen_lock:
            shapes = _seen.get(fn)
            if shapes is None:
                shapes = _seen[fn] = set()
            first = shape not in shapes
            shapes.add(shape)
        fresh = (after > cache_before
                 if cache_before is not None and after is not None
                 else first)
        if not (fresh or first):
            return
        seg = label or "segment"
        reg = _registry()
        if fresh:
            reg.counter("plan.xla_compiles", segment=seg).add()
            reg.histogram("plan.compile_ms",
                          segment=seg).observe(dur_s * 1e3)
        if first:
            # cost capture keys on the per-process memo, not on cache
            # growth: a program compiled before the pillar was enabled
            # (bench warms, then traces) still gets its cost/memory
            # gauges — only the compile TIME is unknowable then
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


# span names the timeline split classifies (all pre-existing seams)
_DISPATCH_SPANS = ("plan/dispatch",)
_H2D_SPANS = ("plan/h2d",)
_D2H_SPANS = ("plan/d2h",)


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


def device_time_split(records: list | None = None) -> dict | None:
    """Compute/transfer/idle attribution of a captured run's plan spans.

    Host-side attribution over the UNION of span intervals — concurrent
    serve lanes (dp>1) emit overlapping ``plan/dispatch`` spans, and a
    naive per-span duration sum would report compute > wall and
    fractions > 1. Attribution precedence inside the occupied union:
    ``plan/h2d`` is transfer, ``plan/dispatch`` time not spent in its
    nested h2d is compute-issue, ``plan/d2h`` time outside both is the
    blocking device→host drains, and ``idle`` is the wall clock no plan
    span covers — the time the host spent between device work (packing,
    queue waits, python). Single-threaded captures decompose exactly as
    a per-span sum would. ``None`` when the capture holds no plan
    spans. Returns milliseconds plus fractions of wall (which now
    always sum to 1)."""
    from mmlspark_tpu.obs.events import SpanRecord

    by_kind: dict[str, list] = {"dispatch": [], "h2d": [], "d2h": []}
    if records is None:
        records = _rt.spans()
    for r in records:
        if not isinstance(r, SpanRecord) or r.cat != "plan":
            continue
        if r.name in _DISPATCH_SPANS:
            by_kind["dispatch"].append((r.start_ns, r.end_ns))
        elif r.name in _H2D_SPANS:
            by_kind["h2d"].append((r.start_ns, r.end_ns))
        elif r.name in _D2H_SPANS:
            by_kind["d2h"].append((r.start_ns, r.end_ns))
    all_iv = by_kind["dispatch"] + by_kind["h2d"] + by_kind["d2h"]
    if not all_iv:
        return None
    u_h2d = _union(by_kind["h2d"])
    u_disp = _union(by_kind["dispatch"])
    u_d2h = _union(by_kind["d2h"])
    wall = max(e for _, e in all_iv) - min(s for s, _ in all_iv)
    h2d = _measure(u_h2d)
    compute = _measure(_subtract(u_disp, u_h2d))
    d2h = _measure(_subtract(_subtract(u_d2h, u_disp), u_h2d))
    idle = max(wall - (compute + h2d + d2h), 0.0)
    out = {
        "wall_ms": round(wall / 1e6, 3),
        "compute_ms": round(compute / 1e6, 3),
        "h2d_ms": round(h2d / 1e6, 3),
        "d2h_ms": round(d2h / 1e6, 3),
        "idle_ms": round(idle / 1e6, 3),
    }
    if wall > 0:
        for key in ("compute", "h2d", "d2h", "idle"):
            out[f"{key}_fraction"] = round(out[f"{key}_ms"] * 1e6 / wall, 4)
    return out
