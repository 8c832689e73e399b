"""A request's way to its first token, stage ``parse`` (median, ms): from the
request body read (``_read_json``) to ``ServingEngine.submit`` putting the
sequence onto the intake queue, on the HANDLER thread: the JSON parse of the
body, validation, tokenisation, ``_allocate_seq``, the engine-wide lock.
Source: the ``first_token`` events of the MEASURED window on the steptrace
ring (``run["window_steps"]`` of a --trace 2 run; one event a request,
field ``parse_ms``), the exact median over the requests whose first token
left in the window. One of six stages that are consecutive differences of
one list of ``time.monotonic()`` stamps and add up to ``total_ms``
(``front.server_ttft_p50_ms``). Layer: HTTP front."""

from lib import first_token


def read(run):
    return first_token.median(run, "first_token", "parse_ms")
