"""``trace_lower_s.setup`` (s): the seconds of set-up spent tracing Python to
jaxprs and lowering them to MLIR: the union of the ``jit/trace`` and
``jit/lower`` intervals before the window (``benchmark/setup_read.py``). What
the models' and kernels' Python costs before the compile cache can answer.
Layer: model code."""

from benchmark import setup_read


def read(run: dict):
    return setup_read.setup_union_seconds(run, ("jit/trace", "jit/lower"))
