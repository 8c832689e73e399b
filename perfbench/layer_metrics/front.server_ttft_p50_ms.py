"""A request's way to its first token inside the server (median, ms): from
the request body read to the handler thread's flush of the SSE event that
carries the first token, the whole that the six stages partition
(``front.parse_p50_ms``, ``engine.intake_wait_p50_ms``,
``sched.first_schedule_wait_p50_ms``, ``runner.first_token_compute_p50_ms``,
``engine.first_token_handover_p50_ms``, ``front.first_token_emit_p50_ms``).
Source: ``total_ms`` of the ``first_token`` events of the MEASURED window
on the steptrace ring (``run["window_steps"]`` of a --trace 2 run), the
exact median. Layer: HTTP front.

Reading it prints the run's ``[first_token]`` line: the MEANS of the six
stages and of ``total_ms`` over the window's events (means add up where
medians do not), how many requests there were, and beside them the
client's mean ``ttft`` over the requests due in the window: what lies
between the two is outside the server (the load generator's lag, the
socket, the header read)."""

import json

from lib import first_token


def read(run):
    n = len(first_token.events(run, "first_token"))
    if n:
        line = {f: round(first_token.mean_over_events(run, "first_token", f),
                         3)
                for f in first_token.STAGES + ("total_ms",)}
        line["requests"] = n
        client, n_client = first_token.client_ttft_mean_ms(run)
        line["client_ttft_mean_ms"] = (None if client is None
                                       else round(client, 3))
        line["client_requests"] = n_client
        print(f"[first_token] means over the measured window: "
              f"{json.dumps(line)}", flush=True)
    return first_token.median(run, "first_token", "total_ms")
