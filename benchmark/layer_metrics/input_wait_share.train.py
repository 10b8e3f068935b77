"""``input_wait_share.train`` (%): the share of the window's ``fit_stream``
loop in which the consumer was blocked waiting for the DeviceLoader, as
the program's own host counter has it
(``Trainer.input_stats["input_bound_fraction"]``). Layer: scheduling."""


def read(run: dict):
    stats = run["window"].get("input_stats")
    if not stats or "input_bound_fraction" not in stats:
        return None
    return 100.0 * float(stats["input_bound_fraction"])
