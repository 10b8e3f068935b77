"""``assemble_share.score`` (%): the share of the scoring window the host
spent putting the outputs together: concatenate, row list, new table
(``transform/assemble`` spans). ``obs.device.host_phase_split`` over the
window's boundary records (``benchmark/span_read.py``), in percent of the
window's seconds. Layer: plan / program."""

from benchmark import span_read


def read(run: dict):
    return span_read.window_share_percent(run, "assemble")
