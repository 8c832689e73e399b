"""What the per-request readers read: the steptrace events of the MEASURED
window (``run["window_steps"]``, kept by a --trace 2 run: every event
whose instant lies in [0, seconds) on the load's clock), one kind at a
time, and a field's exact median and mean over them.

Two kinds are read this way. ``first_token``: one event a request, written
by the thread that ends the request's last stage, with its way to the
first token as ``parse_ms`` / ``intake_ms`` / ``queue_ms`` / ``compute_ms``
/ ``handover_ms`` / ``emit_ms``, consecutive differences of one list of
``time.monotonic()`` stamps that add up to ``total_ms`` (body read ->
flush). ``prefix``: one event a probe of the prefix cache, with the wall
time of the call as ``ms``. A run that kept no events (--trace 1), a
program that writes no such event or field, a window without one: every
reader gives None and none raises.
"""

import statistics

STAGES = ("parse_ms", "intake_ms", "queue_ms", "compute_ms", "handover_ms",
          "emit_ms")


def events(run, kind):
    """The measured window's events of ``kind`` (a list, maybe empty)."""
    return [e for e in run.get("window_steps") or ()
            if e.get("kind") == kind]


def values(run, kind, field):
    """``field`` of every event of ``kind`` in the window that has it."""
    return [e[field] for e in events(run, kind)
            if e.get(field) is not None]


def median(run, kind, field):
    """The exact median of ``field`` over the window's events of ``kind``;
    None where there is nothing to read."""
    vals = values(run, kind, field)
    return statistics.median(vals) if vals else None


def mean_over_events(run, kind, field):
    """The sum of ``field`` over ALL the window's events of ``kind``: an
    event without it (a stage the request did not pass) adds nothing, so
    the stages' means add up to the mean of their total. None where the
    window holds no event of the kind."""
    evs = events(run, kind)
    if not evs:
        return None
    return sum(e.get(field) or 0.0 for e in evs) / len(evs)


def client_ttft_mean_ms(run):
    """Mean ``ttft`` (ms, from the instant a request was DUE to its first
    token's arrival at the load generator) over the requests due in the
    measured window that got one, and how many there were."""
    got = [r.times[0] - r.due for r in run["records"]
           if r.due is not None and 0 <= r.due < run["seconds"]
           and len(r.times)]
    return (1e3 * sum(got) / len(got) if got else None), len(got)
