"""The emitter thread (``serving_engine.EMITTER``): the middle tokens of
an attached stream are written by ONE thread, a step's chunks handed to
it as one list, and everything else (the last chunk, an error, whatever
the sink will not take) reaches the handler thread, in the order it was
put. No engine: handles are fed by hand, so the order is read off lists.
"""

import io
import threading
import time
import types

import pytest

from gllm_tpu.engine.serving_engine import (EMITTER, RequestHandle,
                                            StreamChunk)


def middle(tok):
    return StreamChunk(tok, f"t{tok}", None)


def last(tok):
    return StreamChunk(tok, f"t{tok}", "length")


def drain(handle, timeout=10):
    """What the handler thread would take off ``chunks``, up to and with
    the chunk that ends the stream."""
    out = []
    deadline = time.monotonic() + timeout
    while not out or out[-1].finish_reason is None:
        out.append(handle.chunks.get(timeout=deadline - time.monotonic()))
    return out


def test_an_unattached_stream_is_the_handler_threads_as_it_always_was():
    h = RequestHandle(1, 3)
    batch = []
    for c in (middle(1), middle(2), last(3)):
        h.put(c, batch)
    assert batch == []                      # nothing for the emitter
    assert [c.token_id for c in drain(h)] == [1, 2, 3]


def test_attach_is_refused_once_a_chunk_waits():
    h = RequestHandle(1, 3)
    h.put(middle(1))
    assert not h.attach(lambda c: True)
    h.put(last(2))
    assert [c.token_id for c in drain(h)] == [1, 2]


def test_middle_tokens_go_by_the_sink_and_the_last_chunk_to_the_handler():
    h, seen = RequestHandle(1, 3), []

    def sink(chunk):
        seen.append(("sink", chunk.token_id, threading.current_thread().name))
        return True

    assert h.attach(sink)
    batch = []
    for tok in (1, 2, 3):
        h.put(middle(tok), batch)
    assert len(batch) == 3 and h.chunks.empty()     # one list a step
    EMITTER.post(batch)
    h.put(last(4))                                  # a list of its own
    (end,) = drain(h)
    assert end.token_id == 4
    # the three were on the socket before the handler saw the end
    assert seen == [("sink", t, "gllm-emitter") for t in (1, 2, 3)]


@pytest.mark.parametrize("how", ["hands_back", "raises"])
def test_a_sink_that_will_not_take_a_chunk_is_dropped_for_good(how):
    h, seen = RequestHandle(1, 3), []

    def sink(chunk):
        if chunk.token_id == 2:
            if how == "raises":
                raise BrokenPipeError
            return False
        seen.append(chunk.token_id)
        return True

    assert h.attach(sink)
    batch = []
    for tok in (1, 2, 3, 4):
        h.put(middle(tok), batch)
    h.put(last(5), batch)
    EMITTER.post(batch)
    assert [c.token_id for c in drain(h)] == [2, 3, 4, 5]
    assert seen == [1]


def test_an_error_chunk_passes_the_sink_by():
    h, seen = RequestHandle(1, 3), []
    assert h.attach(lambda c: seen.append(c) or True)
    h.put(StreamChunk(None, "", "error", error="boom"))
    (end,) = drain(h)
    assert end.error == "boom" and seen == []


class Wire:
    """A socket's sending side and the handler's ``wfile`` over one
    buffer. ``takes``: how many bytes each non-blocking ``send`` accepts
    (None: would block); once the list is used up, all of them."""

    def __init__(self, takes=()):
        self.buf, self.takes = io.BytesIO(), list(takes)

    def send(self, data, flags=0):
        n = self.takes.pop(0) if self.takes else len(data)
        if n is None:
            raise BlockingIOError
        self.buf.write(data[:n])
        return min(n, len(data))

    def write(self, data):
        self.buf.write(data)

    def flush(self):
        pass


def stream_over(wire, feed):
    """``Handler._stream`` on a thread of its own over ``wire``; ``feed``
    puts the chunks. Returns the events on the wire."""
    from gllm_tpu.entrypoints.api_server import Handler
    h = Handler.__new__(Handler)
    h.wfile = h.connection = wire
    h.state = types.SimpleNamespace(engine=None)
    handle = RequestHandle(7, 3)
    t = threading.Thread(target=h._stream, args=(
        handle, lambda text, fin: {"text": text, "fin": fin}))
    t.start()
    deadline = time.monotonic() + 10
    while not handle._routed:                # the handler thread attached
        assert time.monotonic() < deadline
        time.sleep(0.001)
    feed(handle)
    t.join(10)
    assert not t.is_alive()
    return wire.buf.getvalue().decode().split("\n\n")


@pytest.mark.parametrize("takes", [(), (None,), (0,), (9,), (None, 5)],
                         ids=["whole", "would_block", "nothing", "a_part",
                              "blocks_then_a_part"])
def test_every_event_is_on_the_wire_once_and_in_order(takes):
    """Whatever the socket takes of the second token's event at once:
    the sink writes what it can, the handler thread sends the rest and
    everything after it."""
    takes = (10 ** 6,) + takes if takes else ()

    def feed(handle):
        batch = []
        for tok in (1, 2, 3):
            handle.put(middle(tok), batch)
        EMITTER.post(batch)
        handle.put(last(4))

    events = stream_over(Wire(takes), feed)
    assert events == [
        'data: {"text": "t1", "fin": null}',
        'data: {"text": "t2", "fin": null}',
        'data: {"text": "t3", "fin": null}',
        'data: {"text": "t4", "fin": "length"}',
        "data: [DONE]", ""]


def test_an_armed_fault_point_sends_the_stream_back_to_the_handler():
    """The chaos points of a stream are in ``_stream``: with one armed
    the sink takes nothing, and the stream is as it was before."""
    from gllm_tpu.faults import FAULTS
    wire = Wire()
    sends = []
    wire.send = lambda data, flags=0: sends.append(data) or len(data)
    FAULTS.arm("replica_hang:1000000")
    try:
        events = stream_over(wire, lambda handle: (
            handle.put(middle(1)), handle.put(last(2))))
    finally:
        FAULTS.reset()
    assert sends == [] and len(events) == 4
