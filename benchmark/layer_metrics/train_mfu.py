"""``train_mfu`` (%): the whole train step's share of the chips' bf16 peak.
Forward and backward operations of the trained rows, from the
configuration's sizes (``benchmark/flops.py``; nothing recomputed is
counted), over the window's seconds, over chips times the peak of
``peaks.json``. Layer: model code. It bounds every kernel's gain: a
later PR that takes a kernel off the path can claim only what shows here."""

from benchmark import flops


def read(run: dict):
    return flops.peak_share_percent(run, flops.train_flops(run["config"]))
