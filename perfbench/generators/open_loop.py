"""Open loop: independent users. Requests are sent on a schedule whether or
not earlier ones have finished, so a slow server's queue grows.

Traffic parameters: ``prompt_len``, ``output_len`` (distributions),
``arrivals`` ({"dist": "exponential"} is Poisson), ``ramp_s``. The cell
gives ``rate_rps``. A request is DUE at its scheduled instant.
"""

import math
import random
import threading

from lib import dist
from lib.loadgen import Req


def plan(traffic, cell, seed, seconds, vocab):
    """Requests with due times in [-ramp_s, seconds), the same sizes and
    gaps for every seed in another order."""
    rate, ramp = cell["rate_rps"], traffic["ramp_s"]
    span = ramp + seconds
    n = max(1, math.ceil(rate * span))
    rng = random.Random(seed)
    gaps = dist.stratified(traffic["arrivals"], n, rng, integer=False)
    scale = span / sum(gaps)            # the plan spans the run exactly
    prompts = dist.stratified(traffic["prompt_len"], n, rng)
    outputs = dist.stratified(traffic["output_len"], n, rng)
    reqs, t = [], -ramp
    for i in range(n):
        t += gaps[i] * scale
        reqs.append(Req(i, dist.tokens(rng, prompts[i], vocab), outputs[i],
                        due=min(t, seconds - 1e-6)))
    return reqs


def start(load, reqs, seconds, traffic):
    """Dispatch ``reqs`` at their due times until the load is stopped."""
    def dispatch():
        for req in reqs:
            load.clock.sleep_until(req.due)
            if load.stopping.is_set() or load.clock.now() >= seconds:
                return
            load.launch(req)

    t = threading.Thread(target=dispatch, daemon=True)
    t.start()
    return [t]
