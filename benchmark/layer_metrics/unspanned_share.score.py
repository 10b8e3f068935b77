"""``unspanned_share.score`` (%): the share of the scoring window no boundary
phase covers: ``transform``'s self time and what the driver does between calls
(its sampling of the answers). ``obs.device.host_phase_split`` over the
window's boundary records (``benchmark/span_read.py``), in percent of the
window's seconds. Layer: entry points."""

from benchmark import span_read


def read(run: dict):
    return span_read.window_share_percent(run, "unspanned")
