"""``programs_built.setup`` (programs): every ``jit/compile`` record before
the window, compiled or loaded from the persistent cache: how many programs a
cell's set-up asks the backend for. Layer: plan / program."""

from benchmark import setup_read


def read(run: dict):
    return setup_read.setup_compiles(run)
