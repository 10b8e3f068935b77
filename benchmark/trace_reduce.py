"""From a profiler trace to device busy time, top operations and idle gaps.

``reduce_events`` is the arithmetic, on plain ``(name, start_ns,
duration_ns)`` tuples, so it is checked on synthetic lists
(``tests/test_harness.py``). ``load_device_events`` is the only part that
knows the profiler's file: it reads the ``.xplane.pb`` with
``jax.profiler.ProfileData`` and keeps, for every device plane, the events
of the line that holds the executed operations.

A device is busy while any operation's interval covers the instant; nested
and overlapping events are merged before they are measured. The sums by
name are not merged, so a parent that contains its children counts both.
Gaps are the idle stretches between merged intervals, longest first; this
benchmark cannot say what the host was doing in them (the program has no
spans on the profiler's clock yet), so each is named ``unattributed``.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
TOP = 10


def merged_intervals(events: list) -> list:
    """Sorted, disjoint ``[start, end]`` intervals covering the events."""
    out: list = []
    for start, dur in sorted((s, d) for _, s, d in events):
        end = start + dur
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def clip_events(events: list, start_ns: float, end_ns: float) -> list:
    """The parts of the events that lie inside ``[start_ns, end_ns]``."""
    out = []
    for name, s, d in events:
        lo, hi = max(s, start_ns), min(s + d, end_ns)
        if hi > lo:
            out.append((name, lo, hi - lo))
    return out


def short_name(name: str) -> str:
    """An operation's name as the profiler printed it, cut to the
    instruction's own name and its first result shape (the profiler gives
    the whole HLO line)."""
    head, _, rest = name.partition(" = ")
    shape = re.search(r"[a-z]+[0-9]*\[[0-9,]*\]", rest)
    return head + (" " + shape.group(0) if shape else "")


def reduce_events(events: list, bounds: tuple | None = None) -> dict:
    """``events``: ``(name, start_ns, duration_ns)`` of one device;
    ``bounds``: the ``(start_ns, end_ns)`` they were clipped to, whose
    head and tail then count among the gaps.

    Returns seconds: ``busy_s`` (the union), ``device_ops`` (``[name, summed seconds]``, the largest
    first, at most ten) and ``idle_gaps`` (``["unattributed", seconds]``,
    the longest first, at most ten)."""
    if not events:
        return {"busy_s": 0.0, "device_ops": [], "idle_gaps": []}
    merged = merged_intervals(events)
    busy = sum(e - s for s, e in merged)
    by_name: dict = {}
    for name, _, dur in events:
        by_name[name] = by_name.get(name, 0) + dur
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]
    edges = merged if bounds is None else (
        [[bounds[0], bounds[0]]] + merged + [[bounds[1], bounds[1]]])
    gaps = sorted((b[0] - a[1] for a, b in zip(edges, edges[1:])),
                  reverse=True)[:TOP]
    return {"busy_s": busy / 1e9,
            "device_ops": [[short_name(n), d / 1e9] for n, d in ops],
            "idle_gaps": [["unattributed", g / 1e9] for g in gaps if g > 0]}


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def load_device_events(path: str) -> dict:
    """``{device plane name: [(name, start_ns, duration_ns), ...]}``."""
    from jax.profiler import ProfileData

    devices: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(DEVICE_PLANE_PREFIX):
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                devices[plane.name] = [
                    (ev.name, ev.start_ns, ev.duration_ns)
                    for ev in line.events]
    return devices


def slice_bounds(devices: dict, settle_s: float, slice_s: float) -> tuple:
    """The slice on the device's clock: from ``settle_s`` after the first
    device operation in the trace, for ``slice_s`` or as far as the trace's
    last operation reaches."""
    first = min(s for ev in devices.values() for _, s, _ in ev)
    last = max(s + d for ev in devices.values() for _, s, d in ev)
    lo = first + settle_s * 1e9
    hi = min(lo + slice_s * 1e9, last)
    if hi <= lo:
        raise RuntimeError("the trace ends before the slice begins: "
                           f"{(last - first) / 1e9:.2f} s of device "
                           f"operations, settle {settle_s} s")
    return lo, hi


def reduce_trace(trace_dir: str, settle_s: float, slice_s: float) -> dict:
    """Reduce the one trace under ``trace_dir`` to its slice (see
    :func:`slice_bounds`): device events are clipped to it, ``window_s`` is
    its length, and busy time is averaged over the device planes found."""
    devices = load_device_events(find_xplane(trace_dir))
    if not any(devices.values()):
        raise RuntimeError("the trace holds no device plane with "
                           f"{OPS_LINE!r} events")
    bounds = slice_bounds(devices, settle_s, slice_s)
    per_device = [reduce_events(clip_events(ev, *bounds), bounds)
                  for ev in devices.values()]
    fullest = max(per_device, key=lambda r: r["busy_s"])
    return {"busy_s": sum(r["busy_s"] for r in per_device) / len(per_device),
            "window_s": (bounds[1] - bounds[0]) / 1e9,
            "devices": len(per_device),
            "device_ops": fullest["device_ops"],
            "idle_gaps": fullest["idle_gaps"]}


def idle_share_percent(trace: dict | None):
    """What a ``device_idle_share`` reader returns: 1 - busy over the
    slice, in percent; ``None`` where nothing ran on the device or no
    trace was taken (never 0 for want of a reading)."""
    if not trace or not trace["window_s"] or not trace["busy_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
