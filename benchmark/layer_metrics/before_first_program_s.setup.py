"""``before_first_program_s.setup`` (s): from the kernel's start of the
process (``obs.runtime.process_start_epoch_ns``) to the start of the first
``jit/*`` record: the interpreter, the imports, the TPU client's start, the
compile cache's placement, before the program traces anything. The
machine's part of ``setup_s``. Layer: entry points."""

from benchmark import setup_read


def read(run: dict):
    records = setup_read.setup_records(run)
    if records is None:
        return None
    from mmlspark_tpu.obs import runtime

    started = runtime.process_start_epoch_ns()
    if started is None:
        return None
    first = min((r for r in records if r.name.startswith("jit/")),
                key=lambda r: r.start_ns)
    return (first.start_epoch_ns - started) / 1e9
