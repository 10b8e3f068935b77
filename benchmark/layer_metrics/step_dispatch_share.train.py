"""``step_dispatch_share.train`` (%): the share of the training window the
host spent inside the dispatch of a step (``train/step`` spans). The call is
asynchronous but blocks once the runtime holds its limit of computations in
flight, so on a device-bound job this is mostly the device's back-pressure;
the dispatches that return at once (the first few dozen of a fit) give the
host's own cost of a step. ``obs.device.host_phase_split`` over the window's
boundary records (``benchmark/span_read.py``), in percent of the window's
seconds. Layer: plan / program."""

from benchmark import span_read


def read(run: dict):
    return span_read.window_share_percent(run, "step_dispatch")
