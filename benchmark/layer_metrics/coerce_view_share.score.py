"""``coerce_view_share.score`` (%): of the rows the scoring window coerced,
the share whose column was handed back as the block it already lies in
instead of being copied. ``rows`` of the window's ``transform/coerce``
boundary records (``benchmark/span_read.py``) whose ``nbytes`` (the bytes
the coercion copied) is 0, over ``rows`` of all that carry an ``nbytes``.
A count, not a time: 0 with a large ``coerce_share.score`` says the table's
rows are separate allocations. A program whose coerce records carry no
``nbytes`` (a commit before the counter) gives nothing to read. Layer: plan
/ program."""

from benchmark import span_read


def read(run: dict):
    records = span_read.window_records(run)
    coerced = [r for r in records or ()
               if r.name == "transform/coerce" and r.nbytes is not None]
    rows = sum(r.rows or 0 for r in coerced)
    if not rows:
        return None
    viewed = sum(r.rows or 0 for r in coerced if r.nbytes == 0)
    return 100.0 * viewed / rows
