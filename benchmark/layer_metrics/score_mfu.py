"""``score_mfu`` (%): the whole scoring pass's share of the chips' bf16
peak. Forward operations of the rows scored, from the configuration's
sizes (``benchmark/flops.py``), over the window's seconds (the host's
feed included, as in ``score_rows_per_s``), over chips times the peak of
``peaks.json``. Layer: model code."""

from benchmark import flops


def read(run: dict):
    return flops.peak_share_percent(run, flops.forward_flops(run["config"]))
