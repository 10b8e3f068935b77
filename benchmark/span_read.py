"""The program's boundary spans of the window, for the per-layer readers.

The program records a small, fixed set of host spans whether or not its
tracer is on (``mmlspark_tpu/obs/spans.boundary_span``; the list is in
``docs/observability.md``) into a process-wide ring, and
``obs.device.host_phase_split`` turns a list of them into seconds and
shares by phase. A reader gets no handle on the program, only ``run``, so
the window's records are found by position: the window is the last thing
the program did before a reader runs (the reference that ``check`` runs
after it is plain JAX and records nothing).

* a scoring window of ``run["window"]["calls"]`` calls: every record under
  the last that many ``transform`` roots;
* a training window of ``run["window"]["steps"]`` steps: the last that many
  ``train/step`` records and the ``train/loss_fetch`` records from the
  first of them on.

Shares are of ``run["window"]["window_s"]``, the driver's own wall, which
begins before the first span and ends after the last. Where the program
has no boundary tier (a commit before it) or the ring holds none of the
window's records, there is nothing to read and everything here returns
``None``.
"""

from __future__ import annotations


def window_records(run: dict) -> list | None:
    """The boundary records of the window, oldest first, or ``None``."""
    from mmlspark_tpu.obs import runtime

    spans = [r for r in runtime.span_records()
             if getattr(r, "root_id", None) is not None]
    window = run["window"]
    if window.get("calls"):
        roots = [r.span_id for r in spans
                 if r.name == "transform" and r.root_id == r.span_id]
        last = set(roots[-int(window["calls"]):])
        return [r for r in spans if r.root_id in last] or None
    if window.get("steps"):
        steps = [r for r in spans
                 if r.name == "train/step"][-int(window["steps"]):]
        if not steps:
            return None
        return steps + [r for r in spans if r.name == "train/loss_fetch"
                        and r.start_ns >= steps[0].start_ns]
    return None


def window_share_percent(run: dict, phase: str):
    """``phase`` of ``host_phase_split`` over the window's records, in
    percent of the window's seconds; ``None`` with nothing to read."""
    try:
        from mmlspark_tpu.obs.device import host_phase_split
    except ImportError:          # the program has no boundary tier yet
        return None
    records = window_records(run)
    if not records or not run["window"].get("window_s"):
        return None
    split = host_phase_split(records, wall_s=run["window"]["window_s"])
    return 100.0 * split[f"{phase}_share"]
