"""``compiles_in_window.train`` (programs): ``jit/trace`` + ``jit/compile``
records that start inside the window (``benchmark/setup_read.py``): 0, or the
window is not steady and ``obs.compile_report()`` names the function.
Layer: plan / program."""

from benchmark import setup_read


def read(run: dict):
    return setup_read.compiles_in_window(run)
