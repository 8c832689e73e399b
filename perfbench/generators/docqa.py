"""Document question answering, closed loop: ``clients`` callers that each
own ONE document and put question after question to it, each waiting for
the reply before sending the next, so a slow server receives less load.

Every request of a caller is its document followed by a fresh question:
the first one prefills the document (in the fill), and every later one
finds the document's whole pages in the server's prefix cache and computes
only the question. ``closed_loop.py`` draws fresh tokens for every request
and cannot say that; the fill, the due times and the window are its own,
step for step.

Traffic parameters: ``document_len`` (one draw a caller), ``question_len``
and ``output_len`` (one a request), ``requests_per_client``, ``ramp_s`` (the
least time between the first caller and the window), ``first.output_len``
(the lengths of each caller's first answer). The cell gives ``clients``. A
request is DUE at the instant its client's previous one ended. Every seed
gets the same multisets of lengths in another order (``lib/dist.py``).

- The callers connect one by one, each when the one before has its first
  token, so the documents are prefilled one after another, in the server's
  chunks, beside the rows already decoding.
- ``first.output_len`` spreads the first answers evenly; its minimum keeps
  anybody from finishing while the others are still connecting, and the
  spread leaves the callers out of step when the window opens.

A request's prompt says how many of its leading tokens are the caller's
document (``shared``): what a reader needs to count the work of a request
that hit the cache. It is put together when the request is sent
(``Prompt``, a list: what the load generator sends), and before that and
after the request has ended it holds its lengths only (``Unsent``): the
plan of a run is a few thousand requests of 8-16 k tokens.

``start`` returns the event that is set when the last caller has connected;
the window opens after that, never at a planned instant.
"""

import random
import threading

from lib import dist
from lib.loadgen import Req

OPENS_WHEN_READY = True


class Prompt(list):
    """Document + question, as sent; the ``shared`` leading tokens are the
    document's."""
    __slots__ = ("shared",)


class Unsent:
    """A prompt that is not in flight: its length, the document's, and,
    until it is sent, the document (shared by the caller's requests) and
    the question."""
    __slots__ = ("n", "shared", "doc", "question")

    def __init__(self, n, shared, doc=None, question=None):
        self.n, self.shared, self.doc, self.question = n, shared, doc, question

    def __len__(self):
        return self.n

    def whole(self):
        prompt = Prompt(self.doc)
        prompt.extend(self.question)
        prompt.shared = self.shared
        return prompt


def plan(traffic, cell, seed, seconds, vocab):
    """Per client its document and its requests; due times are set as the
    run goes. The first answers take ``first.output_len``, the later ones
    the mix's."""
    clients, per = cell["clients"], traffic["requests_per_client"]
    rng = random.Random(seed)
    docs = dist.stratified(traffic["document_len"], clients, rng)
    questions = dist.stratified(traffic["question_len"], clients * per, rng)
    outputs = dist.stratified(traffic["output_len"], clients * (per - 1),
                              rng)
    first_outputs = dist.stratified(traffic["first"]["output_len"], clients,
                                    rng)
    reqs = []
    for client in range(clients):
        doc = dist.tokens(rng, docs[client], vocab)
        for i in range(per):
            question = dist.tokens(rng, questions.pop(), vocab)
            out = first_outputs[client] if i == 0 else outputs.pop()
            reqs.append(Req(
                len(reqs), Unsent(len(doc) + len(question), len(doc), doc,
                                  question), out, client=client))
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
                req.prompt = req.prompt.whole()
                load.records.append(req)
            load.stream(req)
            with load.lock:
                req.prompt = Unsent(len(req.prompt), req.prompt.shared)
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
