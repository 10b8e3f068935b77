"""``gc_pause_share.score`` (%): the seconds of the window the host spent in
the cyclic collector (``host/gc`` records: full collections, and young ones
of 1 ms and longer), in percent of the window's seconds. Layer: entry
points."""

from benchmark import setup_read


def read(run: dict):
    return setup_read.gc_pause_share_percent(run)
