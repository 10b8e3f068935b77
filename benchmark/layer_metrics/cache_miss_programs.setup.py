"""``cache_miss_programs.setup`` (programs): the ``jit/compile`` records
before the window that the persistent cache did not answer
(``cached`` false). 0 on a warm start is the state wanted; on a first run it
is what ``first_setup_s`` paid for. Layer: plan / program."""

from benchmark import setup_read


def read(run: dict):
    return setup_read.setup_compiles(run, cached=False)
