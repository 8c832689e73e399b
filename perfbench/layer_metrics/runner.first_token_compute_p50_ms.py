"""A request's way to its first token, stage ``compute`` (median, ms): from the
first schedule to the collect that brought the first token
(``LLM._observe_outputs``): the steps that carried a chunk of the prompt
(the event's ``chunks``), their ``build`` and ``dispatch``, and the passes
between them.
Source: the ``first_token`` events of the MEASURED window on the steptrace
ring (``run["window_steps"]`` of a --trace 2 run; one event a request,
field ``compute_ms``), the exact median over the requests whose first token
left in the window. One of six stages that are consecutive differences of
one list of ``time.monotonic()`` stamps and add up to ``total_ms``
(``front.server_ttft_p50_ms``). Layer: runner."""

from lib import first_token


def read(run):
    return first_token.median(run, "first_token", "compute_ms")
