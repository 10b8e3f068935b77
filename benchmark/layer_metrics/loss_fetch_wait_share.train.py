"""``loss_fetch_wait_share.train`` (%): the share of the training window the
host spent blocked on a lagged loss scalar (``train/loss_fetch`` spans). The
fetches inside a fit find a scalar a log interval old; the one that closes the
fit waits for the device to catch up. ``obs.device.host_phase_split`` over the
window's boundary records (``benchmark/span_read.py``), in percent of the
window's seconds. Layer: scheduling."""

from benchmark import span_read


def read(run: dict):
    return span_read.window_share_percent(run, "loss_fetch")
