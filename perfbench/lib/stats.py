"""From per-request records to the window's numbers.

The window is [0, seconds) on the load's clock. It counts what happened
inside it: tokens that arrived in it, requests that were due in it. A rate
is taken over all the work and all the time of the window, and a tail is
the tail of all its requests: a request that failed, was refused or had no
first token when the run stopped waiting counts with the worst time seen.
"""

import math


def percentile(values, p):
    """The ``p``-th percentile (0..100), linear between closest ranks."""
    vals = sorted(values)
    if not vals:
        return None
    k = (len(vals) - 1) * p / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return vals[lo] + (vals[hi] - vals[lo]) * (k - lo)


def due_in_window(records, seconds):
    return [r for r in records if 0 <= r.due < seconds]


def ttfts(records, seconds):
    """Seconds from due to first token for every request due in the
    window; one without a first token gets the longest wait observed."""
    due = due_in_window(records, seconds)
    got = [r.times[0] - r.due for r in due if len(r.times)]
    missing = [r for r in due if not len(r.times)]
    if missing:
        waited = [(r.ended if r.ended is not None else seconds) - r.due
                  for r in missing]
        worst = max(got + waited)
        got += [worst] * len(missing)
    return got


def gaps(records, seconds):
    """Every gap between consecutive tokens of a stream whose later token
    arrived inside the window."""
    out = []
    for r in records:
        t = r.times
        out.extend(t[i] - t[i - 1] for i in range(1, len(t))
                   if 0 <= t[i] < seconds)
    return out


def tokens_in(records, lo, hi):
    return sum(1 for r in records for t in r.times if lo <= t < hi)


def lags(records, seconds):
    """How late the generator sent each request due in the window."""
    return [r.sent - r.due for r in due_in_window(records, seconds)
            if r.sent is not None]


def attempts(records, seconds):
    due = due_in_window(records, seconds)
    return len(due), sum(r.status.startswith("failed") for r in due)


def end_to_end(records, seconds):
    first, gap = ttfts(records, seconds), gaps(records, seconds)
    ms = lambda v: None if v is None else 1e3 * v   # noqa: E731
    return {
        "output_tok_s": tokens_in(records, 0, seconds) / seconds,
        "ttft_p50_ms": ms(percentile(first, 50)),
        "ttft_p90_ms": ms(percentile(first, 90)),
        "itl_p95_ms": ms(percentile(gap, 95)),
    }


def describe(records, seconds):
    """Sample counts and the lengths drawn, for the lines above the
    result."""
    due = due_in_window(records, seconds)
    by_status = {}
    for r in records:
        key = r.status.split(":")[0]
        by_status[key] = by_status.get(key, 0) + 1
    lag = lags(records, seconds)
    return {
        "requests_sent": len(records), "due_in_window": len(due),
        "by_status": by_status,
        "ttft_samples": len(ttfts(records, seconds)),
        "gap_samples": len(gaps(records, seconds)),
        "tokens_in_window": tokens_in(records, 0, seconds),
        "prompt_len_p50": percentile([len(r.prompt) for r in due], 50),
        "prompt_len_max": max((len(r.prompt) for r in due), default=None),
        "output_len_p50": percentile([r.max_tokens for r in due], 50),
        "lag_p95_ms": (1e3 * percentile(lag, 95) if lag else None),
    }


def dump(records):
    """Per-request records for the file beside the result."""
    return [{"idx": r.idx, "client": r.client, "due": r.due, "sent": r.sent,
             "prompt_len": len(r.prompt), "max_tokens": r.max_tokens,
             "n_tokens": len(r.times),
             "first": r.times[0] if len(r.times) else None,
             "last": r.times[-1] if len(r.times) else None,
             "status": r.status} for r in records]
