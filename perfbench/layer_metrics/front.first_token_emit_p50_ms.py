"""A request's way to its first token, stage ``emit`` (median, ms): from
``deliver_output``'s stamp on the first chunk (engine thread) to the handler
thread's flush of the SSE event that carries it: the handler's wake-up,
``json.dumps``, the socket.
Source: the ``first_token`` events of the MEASURED window on the steptrace
ring (``run["window_steps"]`` of a --trace 2 run; one event a request,
field ``emit_ms``), the exact median over the requests whose first token
left in the window. One of six stages that are consecutive differences of
one list of ``time.monotonic()`` stamps and add up to ``total_ms``
(``front.server_ttft_p50_ms``). Layer: HTTP front."""

from lib import first_token


def read(run):
    return first_token.median(run, "first_token", "emit_ms")
