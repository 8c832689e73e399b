"""Closed loop: ``clients`` callers that each wait for a reply before
sending the next request, so a slow server receives less load.

Traffic parameters: ``prompt_len``, ``output_len``, ``requests_per_client``,
``ramp_s`` (the least time between the first caller and the window),
``first`` (the lengths of each caller's first request). The cell gives
``clients``. A request is DUE at the instant its client's previous one ended.

The fill is the same sequence of steps whatever the seed and however fast
the host: the server builds a program per (tokens, rows, pages) bucket, and
a bucket that only some runs pass through is a stall in those runs.

- The callers connect one by one, each when the one before has its first
  token, so every joining step carries exactly one prompt; with
  ``first.prompt_len`` fixed, all of them have one tokens bucket and one
  pages bucket.
- ``first.output_len`` spreads the first answers evenly from well under the
  mix's shortest up to its longest, so that the callers are out of step
  when the window opens and the batch never drains; its minimum keeps
  anybody from finishing while the others are still connecting.

``start`` returns the event that is set when the last caller has connected;
the window opens after that, never at a planned instant.
"""

import random
import threading

from lib import dist
from lib.loadgen import Req

OPENS_WHEN_READY = True


def plan(traffic, cell, seed, seconds, vocab):
    """Per client a list of requests; due times are set as the run goes.
    Every seed gets the same lengths in another order: the first requests
    ``first``'s own, the later ones the mix's."""
    clients, per = cell["clients"], traffic["requests_per_client"]
    rng = random.Random(seed)
    first, later = traffic["first"], clients * (per - 1)
    prompts = dist.stratified(traffic["prompt_len"], later, rng)
    outputs = dist.stratified(traffic["output_len"], later, rng)
    first_prompts = dist.stratified(first["prompt_len"], clients, rng)
    first_outputs = dist.stratified(first["output_len"], clients, rng)
    reqs = []
    for client in range(clients):
        lens = [(first_prompts[client], first_outputs[client])]
        lens += [(prompts.pop(), outputs.pop()) for _ in range(per - 1)]
        for prompt, out in lens:
            reqs.append(Req(len(reqs), dist.tokens(rng, prompt, vocab), out,
                            client=client))
    return reqs


def start(load, reqs, seconds, traffic):
    """One thread per client, each sending its requests back to back.
    Returns the event that says the fill is over."""
    by_client = {}
    for r in reqs:
        by_client.setdefault(r.client, []).append(r)

    def run(mine, before, joined):
        if before is not None:
            before.wait()
        mine[0].first = joined
        prev = None
        for req in mine:
            if load.stopping.is_set() or (
                    load.opened.is_set() and load.clock.now() >= seconds):
                break
            with load.lock:
                req.due = load.clock.now() if prev is None else prev.ended
                load.records.append(req)
            load.stream(req)
            prev = req
        joined.set()                # never leave the next caller waiting

    threads, before = [], None
    for client in sorted(by_client):
        joined = threading.Event()
        threads.append(threading.Thread(
            target=run, args=(by_client[client], before, joined),
            daemon=True))
        before = joined
    for t in threads:
        t.start()
    load.threads.extend(threads)
    return before
