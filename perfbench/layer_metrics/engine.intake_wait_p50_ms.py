"""A request's way to its first token, stage ``intake`` (median, ms): from the
put onto the intake queue to ``llm.add_seq`` returning in
``ServingEngine._drain_intake``, on the ENGINE thread: the loop's pass
coming round, which is the step that is running.
Source: the ``first_token`` events of the MEASURED window on the steptrace
ring (``run["window_steps"]`` of a --trace 2 run; one event a request,
field ``intake_ms``), the exact median over the requests whose first token
left in the window. One of six stages that are consecutive differences of
one list of ``time.monotonic()`` stamps and add up to ``total_ms``
(``front.server_ttft_p50_ms``). Layer: engine loop."""

from lib import first_token


def read(run):
    return first_token.median(run, "first_token", "intake_ms")
