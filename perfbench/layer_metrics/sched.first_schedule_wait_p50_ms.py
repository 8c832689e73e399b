"""A request's way to its first token, stage ``queue`` (median, ms): from
``llm.add_seq`` returning to the request's first schedule
(``Sequence.first_sched_time``): the probe of the prefix cache and the
admission passes that went by without it (the event's ``passes_waited``).
Source: the ``first_token`` events of the MEASURED window on the steptrace
ring (``run["window_steps"]`` of a --trace 2 run; one event a request,
field ``queue_ms``), the exact median over the requests whose first token
left in the window. One of six stages that are consecutive differences of
one list of ``time.monotonic()`` stamps and add up to ``total_ms``
(``front.server_ttft_p50_ms``). Layer: scheduler."""

from lib import first_token


def read(run):
    return first_token.median(run, "first_token", "queue_ms")
