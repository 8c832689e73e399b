"""What the per-layer readers read from: helpers over a run's sources.

``run`` (built in run.py) holds: ``records`` (the generator's per-request
records), ``prom0``/``prom1`` (/metrics at the window's ends), ``steps``
(/steptrace events of the window), ``kv_util`` (the gauge, once a second),
``trace`` (trace_reduce.py's output, or None), ``slice`` (the traced part of
the window on the load's clock), ``model``, ``config``, ``cell``, ``peaks``.
"""

from lib.serving import prom_histogram, prom_samples


def counter_delta(run, name, label=None):
    """Growth of a counter over the window, summed over its labels (or for
    the one label string given). None where /metrics was not read."""
    if run["prom0"] is None or run["prom1"] is None:
        return None

    def total(text):
        samples = prom_samples(text, name)
        if label is not None:
            return samples.get(label, 0.0)
        return sum(samples.values())
    return total(run["prom1"]) - total(run["prom0"])


def histogram_quantile(run, name, q):
    """The ``q``-quantile of a histogram's growth over the window, linear
    inside the bucket it falls in. None where nothing was observed."""
    if run["prom0"] is None or run["prom1"] is None:
        return None
    before = dict(prom_histogram(run["prom0"], name))
    delta = [(b, c - before.get(b, 0.0))
             for b, c in prom_histogram(run["prom1"], name)]
    if not delta or delta[-1][1] <= 0:
        return None
    target = q * delta[-1][1]
    lo_b, lo_c = 0.0, 0.0
    for bound, cum in delta:
        if cum >= target:
            if bound == float("inf"):
                return lo_b
            share = (target - lo_c) / (cum - lo_c) if cum > lo_c else 1.0
            return lo_b + share * (bound - lo_b)
        lo_b, lo_c = bound, cum
    return None


def step_ms(run, cls):
    """Device durations (ms) of the step programs of class ``cls``, over
    all devices of the trace."""
    if not run["trace"]:
        return []
    return [ms for d in run["trace"]["devices"].values()
            for ms in d["step_ms"].get(cls, [])]


def kernel_seconds(run, kernel):
    """Device seconds and calls of one kernel, summed over devices."""
    if not run["trace"]:
        return 0.0, 0
    rows = [d["kernels"].get(kernel) for d in run["trace"]["devices"].values()]
    rows = [r for r in rows if r]
    return sum(r["seconds"] for r in rows), sum(r["calls"] for r in rows)


def decode_contexts(run):
    """Context length (tokens attended) of every token that arrived inside
    the traced slice and came from a decode step (every token of a stream
    but its first, which the prefill step emits)."""
    lo, hi = run["slice"]
    return [len(r.prompt) + j for r in run["records"]
            for j, t in enumerate(r.times) if j >= 1 and lo <= t < hi]


def prefills_in_slice(run):
    """Prompt lengths of the requests whose first token arrived inside the
    traced slice: the prompts prefilled in it, up to one at each edge."""
    lo, hi = run["slice"]
    return [len(r.prompt) for r in run["records"]
            if len(r.times) and lo <= r.times[0] < hi]
