"""``device_idle_share.score`` (%): the share of the traced slice of the
window in which no operation ran on the device: 1 - the union of the
device operations' intervals (``benchmark/trace_reduce.py``) over the
slice's length on the host's clock. Layer: device."""


from benchmark import trace_reduce


def read(run: dict):
    return trace_reduce.idle_share_percent(run["trace"])
