"""A request's way to its first token, stage ``handover`` (median, ms): from
the collect that brought the first token to ``deliver_output``'s stamp on
its chunk: since the hand-over moved behind the next step's dispatch, that
step's ``schedule`` + ``build`` + ``dispatch``.
Source: the ``first_token`` events of the MEASURED window on the steptrace
ring (``run["window_steps"]`` of a --trace 2 run; one event a request,
field ``handover_ms``), the exact median over the requests whose first token
left in the window. One of six stages that are consecutive differences of
one list of ``time.monotonic()`` stamps and add up to ``total_ms``
(``front.server_ttft_p50_ms``). Layer: engine loop."""

from lib import first_token


def read(run):
    return first_token.median(run, "first_token", "handover_ms")
