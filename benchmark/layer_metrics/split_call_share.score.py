"""``split_call_share.score`` (%): of the scoring window's calls, the share
in which a minibatch went to the device in pieces, so that the device could
start on the first piece while the rest was still on the link. A call is a
``transform`` root among the window's boundary records
(``benchmark/span_read.py``) that says how many ``minibatches`` its rows
made; it was split when more ``plan/h2d`` records carry its ``root_id``
than it has minibatches. A count, not a time: 100 with a large
``device_idle_share.score`` says the pieces do not hide the upload; 0 says
every minibatch crossed whole (small minibatches, or a program before the
mechanism). No root that counts its minibatches gives nothing to read.
Layer: plan / program."""

from collections import Counter

from benchmark import span_read


def read(run: dict):
    records = span_read.window_records(run) or ()
    calls = [r for r in records
             if r.name == "transform" and r.root_id == r.span_id
             and getattr(r, "minibatches", None) is not None]
    if not calls:
        return None
    uploads = Counter(r.root_id for r in records if r.name == "plan/h2d")
    split = sum(uploads[r.span_id] > r.minibatches for r in calls)
    return 100.0 * split / len(calls)
