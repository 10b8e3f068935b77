"""``backend_compile_s.setup`` (s): the seconds of set-up inside XLA's backend
compile or the persistent cache's load: the union of the ``jit/compile``
intervals before the window (``benchmark/setup_read.py``). Layer: plan /
program."""

from benchmark import setup_read


def read(run: dict):
    return setup_read.setup_union_seconds(run, ("jit/compile",))
