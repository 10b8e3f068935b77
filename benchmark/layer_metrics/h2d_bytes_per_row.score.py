"""``h2d_bytes_per_row.score`` (bytes/row): the bytes the scoring window
uploaded (``nbytes`` on its ``plan/h2d`` boundary records) over the rows its
calls were handed (``rows`` on its ``transform`` roots). A count, not a
time: padding, a wider dtype or a second upload of a row all show here.
``plan.h2d_bytes`` / ``transform.rows`` of ``obs.registry()`` is the same
ratio over the whole process. Layer: plan / program."""

from benchmark import span_read


def read(run: dict):
    records = span_read.window_records(run)
    if not records:
        return None
    rows = sum(r.rows or 0 for r in records if r.name == "transform")
    sent = sum(r.nbytes or 0 for r in records if r.name == "plan/h2d")
    return sent / rows if rows and sent else None
