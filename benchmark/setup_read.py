"""The program's compile tier, for the per-layer readers of set-up.

Beside its boundary spans (``benchmark/span_read.py``) the program records,
with its tracer off, every trace, lowering and backend compile (or
persistent-cache load) JAX makes as ``jit/trace`` / ``jit/lower`` /
``jit/compile`` records named by function, and the collector's pauses as
``host/gc``, into the same ring on the same clock
(``mmlspark_tpu/obs/compile_tier.py``). Set-up is everything before the
window, so it is found by position, as the window is:

* :func:`window_bounds`: the start of the first and the end of the last
  record ``span_read.window_records`` returns;
* :func:`setup_records`: the tier's records that end before the window
  starts (the driver's building, warming and measuring of its own costs);
* :func:`window_tier_records`: those that start inside it (none, on a
  steady window, but pauses). What the reference compiles after the
  window is in neither.

Each is ``None`` when there is nothing sound to read: the program has no
compile tier (a commit before it), the ring holds no record of the window,
or the ring is full (records may have been evicted, and a partial sum is
never given for a whole one).
"""

from __future__ import annotations

from benchmark import span_read


def _tier():
    try:
        from mmlspark_tpu.obs import compile_tier
    except ImportError:          # the program has no compile tier yet
        return None
    return compile_tier


def window_bounds(run: dict) -> tuple | None:
    """``(start_ns, end_ns)`` of the window on the spans' clock."""
    records = span_read.window_records(run)
    if not records:
        return None
    return (min(r.start_ns for r in records),
            max(r.end_ns for r in records))


def _split(run: dict) -> tuple | None:
    """``(tier, its records, the window's bounds)``, or ``None``."""
    from mmlspark_tpu.obs import runtime

    tier = _tier()
    bounds = window_bounds(run)
    if tier is None or bounds is None or runtime.ring_full():
        return None
    return tier, tier.tier_records(), bounds


def setup_records(run: dict) -> list | None:
    """The tier's records that ended before the window started; ``None``
    also when no program was built before it."""
    found = _split(run)
    if found is None:
        return None
    tier, records, (start, _end) = found
    before = [r for r in records if r.end_ns <= start]
    return before if any(r.name in tier.JIT_NAMES for r in before) else None


def window_tier_records(run: dict) -> list | None:
    """The tier's records that started inside the window."""
    found = _split(run)
    if found is None:
        return None
    _tier_module, records, (start, end) = found
    return [r for r in records if start <= r.start_ns <= end]


def setup_union_seconds(run: dict, names: tuple):
    """Seconds the set-up's records named in ``names`` cover together (a
    trace holds the traces of the jitted functions it calls: a plain sum
    counts those twice)."""
    records = setup_records(run)
    if records is None:
        return None
    return _tier().union_seconds(records, names)


def setup_compiles(run: dict, cached: bool | None = None):
    """How many ``jit/compile`` records set-up left; with ``cached``
    given, those whose persistent-cache flag equals it."""
    records = setup_records(run)
    if records is None:
        return None
    return sum(1 for r in records if r.name == "jit/compile"
               and (cached is None
                    or bool(r.labels.get("cached")) == cached))


def compiles_in_window(run: dict):
    """``jit/trace`` + ``jit/compile`` records that started inside the
    window: 0, or the window is not steady."""
    records = window_tier_records(run)
    if records is None:
        return None
    return sum(1 for r in records
               if r.name in ("jit/trace", "jit/compile"))


def gc_pause_share_percent(run: dict):
    """The collector's pauses that began inside the window, in percent of
    the window's seconds."""
    records = window_tier_records(run)
    if records is None or not run["window"].get("window_s"):
        return None
    paused = sum(r.dur_ns for r in records if r.name == "host/gc")
    return 100.0 * paused / 1e9 / run["window"]["window_s"]
