"""How long a request waits for a slot: p90 of the program's ``queue_wait``
records, arrival (the time the request was due) to admission
(``Request.admit_time``), on the scheduler's clock. With ``prefill_wait``
it adds up to the time to first token. Over the requests admitted in the
pre-roll and the untraced part of the window; left out are the set-up's
two checked requests and those admitted to the empty server at the start
of the pre-roll (``lib/program_spans.py``)."""

from benchmarks.lib import program_spans


def read(ctx):
    return program_spans.request_wait_ms("queue_wait", 90)
