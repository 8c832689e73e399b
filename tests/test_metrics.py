"""Observability layer: registry semantics, histogram buckets, Prometheus
rendering, steptrace ring rollover, and the CPU-only /metrics smoke check
(boots a dummy-weight engine, generates, scrapes, and fails on
unregistered or duplicate metric names)."""

import http.client
import json
import math
import threading

import pytest

from gllm_tpu.obs import metrics as obs
from gllm_tpu.obs.metrics import (Counter, Gauge, Histogram, Registry,
                                  parse_exposition, percentile)
from gllm_tpu.obs.steptrace import StepTrace, summarize


# ---- registry semantics ---------------------------------------------------

def test_registry_idempotent_and_conflicts():
    reg = Registry()
    c1 = obs.counter("x_total", "a counter", registry=reg)
    c2 = obs.counter("x_total", "a counter", registry=reg)
    assert c1 is c2
    with pytest.raises(ValueError):
        obs.gauge("x_total", "now a gauge", registry=reg)
    with pytest.raises(ValueError):
        obs.counter("x_total", "different labels", ("kind",),
                    registry=reg)
    h1 = obs.histogram("h_seconds", "h", buckets=(0.1, 1.0),
                       registry=reg)
    assert obs.histogram("h_seconds", "h", buckets=(0.1, 1.0),
                         registry=reg) is h1
    with pytest.raises(ValueError):
        obs.histogram("h_seconds", "h", buckets=(0.5, 5.0),
                      registry=reg)


def test_counter_gauge_basics():
    reg = Registry()
    c = obs.counter("req_total", "requests", ("kind",), registry=reg)
    c.inc(kind="a")
    c.inc(2, kind="a")
    c.inc(kind="b")
    assert c.get(kind="a") == 3
    assert c.get(kind="b") == 1
    with pytest.raises(ValueError):
        c.inc(-1, kind="a")
    with pytest.raises(ValueError):
        c.inc(wrong="label")
    g = obs.gauge("depth", "queue depth", registry=reg)
    g.set(7)
    g.dec()
    assert g.get() == 6
    # .labels() child API
    c.labels(kind="a").inc(10)
    assert c.get(kind="a") == 13


def test_counter_thread_safety():
    reg = Registry()
    c = obs.counter("t_total", "threaded", registry=reg)

    def spin():
        for _ in range(5000):
            c.inc()

    ts = [threading.Thread(target=spin) for _ in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.get() == 40000


# ---- histograms -----------------------------------------------------------

def test_histogram_buckets_and_percentile():
    reg = Registry()
    h = obs.histogram("lat_seconds", "latency", buckets=(0.01, 0.1, 1.0),
                      registry=reg)
    for v in (0.005, 0.05, 0.05, 0.5, 5.0):
        h.observe(v)
    counts, total, count = h.snapshot()
    assert counts == [1, 2, 1, 1]          # per-bucket, +Inf last
    assert count == 5
    assert math.isclose(total, 5.605)
    # median falls in the (0.01, 0.1] bucket
    p50 = percentile(h, 0.5)
    assert 0.01 < p50 <= 0.1
    # top-bucket observations clamp to the last finite bound
    assert percentile(h, 0.999) == 1.0
    # windowed percentile via snapshot diff
    before = h.snapshot()
    h.observe(0.002)
    assert percentile(h, 0.5, before=before) <= 0.01
    assert percentile(obs.histogram("empty_seconds", "e", registry=reg),
                      0.5) is None


def test_histogram_rejects_bad_buckets():
    with pytest.raises(ValueError):
        Histogram("h", "h", buckets=())
    with pytest.raises(ValueError):
        Histogram("h", "h", buckets=(1.0, 1.0))


# ---- Prometheus rendering -------------------------------------------------

def test_prometheus_rendering():
    reg = Registry()
    c = obs.counter("gen_total", "things\nwith newline", ("kind",),
                    registry=reg)
    c.inc(3, kind='a"b')
    h = obs.histogram("dur_seconds", "dur", buckets=(0.1, 1.0),
                      registry=reg)
    h.observe(0.05)
    h.observe(0.5)
    text = reg.render()
    assert '# HELP gen_total things\\nwith newline' in text
    assert "# TYPE gen_total counter" in text
    assert 'gen_total{kind="a\\"b"} 3' in text
    assert "# TYPE dur_seconds histogram" in text
    assert 'dur_seconds_bucket{le="0.1"} 1' in text
    assert 'dur_seconds_bucket{le="1"} 2' in text
    assert 'dur_seconds_bucket{le="+Inf"} 2' in text
    assert "dur_seconds_count 2" in text
    typed, samples, dupes = parse_exposition(text)
    assert not dupes
    assert typed["gen_total"] == "counter"
    assert samples[("dur_seconds_count", "")] == 2


# ---- steptrace ring -------------------------------------------------------

@pytest.mark.parametrize("rows,impl,want", [
    # 3 decoding rows, a chunk, and a final chunk of one token behind it
    ([1, 1, 1, 7, 1], "pallas", (3, 2)),
    # a spec-decode row (1 + 2 drafts) is the ragged kernel's
    ([1, (1, 2), 1], "pallas", (1, 2)),
    # a decode-only step is the decode kernel's whole: nothing to count
    ([1, 1, 1], "pallas", (0, 0)),
    # no kernel serves a row where attention is not on the Pallas path
    ([1, 1, 1, 7, 1], "xla", (0, 0)),
], ids=["mixed", "drafts", "decode_only", "xla"])
def test_mixed_step_rows_counter(rows, impl, want):
    """``gllm_mixed_step_rows_total``: a mixed step's sequences by the
    kernel the dispatch gives them, by the rule the device reads off
    ``cu_q_lens`` (ops/attention._mixed_step_attention)."""
    from types import SimpleNamespace
    from gllm_tpu.engine import llm
    from gllm_tpu.scheduler import ScheduledBatch, ScheduledSeq

    items = []
    for r in rows:
        n, drafts = r if isinstance(r, tuple) else (r, 0)
        items.append(ScheduledSeq(SimpleNamespace(seq_id=len(items)), n, 0,
                                  draft_tokens=(0,) * drafts))
    batch = ScheduledBatch(items)
    engine = SimpleNamespace(runner=SimpleNamespace(attn_impl=impl),
                             tracing=False)
    read = lambda: tuple(llm._M_MIXED_ROWS.get(kernel=k)
                         for k in ("decode", "ragged"))
    before = read()
    llm.LLM._emit_step(engine, "prefill", {"tokens": batch.total_tokens},
                       [batch], {"t_enter": 0.0}, 0.0, 0.0, 0.001)
    assert tuple(a - b for a, b in zip(read(), before)) == want


def test_steptrace_ring_rollover():
    tr = StepTrace(capacity=8)
    for i in range(20):
        tr.record("decode", tokens=i)
    assert len(tr) == 8
    assert tr.dropped == 12
    evs = tr.events()
    assert [e["tokens"] for e in evs] == list(range(12, 20))
    assert [e["seq"] for e in evs] == list(range(12, 20))
    # mark/since brackets a window even across rollover
    mark = tr.mark()
    tr.record("prefill", tokens=99)
    window = tr.events(since=mark)
    assert len(window) == 1 and window[0]["kind"] == "prefill"
    # since older than the ring clamps to what survives
    assert len(tr.events(since=0)) == 8
    tr.clear()
    assert len(tr) == 0 and tr.mark() == 0


def test_steptrace_summarize():
    tr = StepTrace(capacity=64)
    tr.record("prefill", tokens=512, wall_ms=30.0, num_seqs=4)
    for _ in range(3):
        tr.record("decode", tokens=8, wall_ms=90.0, num_seqs=8)
    tr.record("fused_block", tokens=64, wall_ms=88.0, k=8, num_seqs=8)
    tr.record("compile", dispatch="step")
    tr.record("chain_break", num_seqs=8)
    s = summarize(tr.events())
    assert s["by_kind"]["decode"]["steps"] == 3
    assert s["by_kind"]["decode"]["ms_per_step"] == 90.0
    assert s["decode_steps_unfused"] == 3
    assert s["decode_substeps_fused"] == 8
    # 270 unfused ms of 358 decode ms — the r5 "18/59" class of readout
    assert abs(s["unfused_decode_wall_frac"] - 270.0 / 358.0) < 1e-4
    assert s["compiles"] == 1 and s["chain_breaks"] == 1


def test_dump_helper(tmp_path, capsys):
    from gllm_tpu.obs import dump
    tr = StepTrace(capacity=16)
    tr.record("decode", tokens=4, wall_ms=1.5, num_seqs=4)
    tr.record("fused_block", tokens=32, wall_ms=3.0, k=8, num_seqs=4)
    p = tmp_path / "trace.jsonl"
    tr.to_jsonl(str(p))
    assert dump.main([str(p)]) == 0
    out = capsys.readouterr().out
    assert "fused_block" in out
    summary = json.loads(out[out.index("{"):])
    assert summary["by_kind"]["decode"]["steps"] == 1
    # the /steptrace JSON payload shape is accepted too
    p2 = tmp_path / "payload.json"
    p2.write_text(json.dumps({"events": tr.events()}))
    assert dump.main([str(p2), "--summary"]) == 0


# ---- CPU-only engine smoke (tier-1 safe) ----------------------------------

@pytest.fixture(scope="module")
def obs_server():
    """Dummy-weight tiny engine behind a live api_server (no torch, no
    tokenizer — token-array prompts)."""
    from gllm_tpu.config import CacheConfig, EngineConfig
    from gllm_tpu.engine.llm import LLM
    from gllm_tpu.entrypoints.api_server import serve
    from gllm_tpu.models.config import ModelConfig

    model_cfg = ModelConfig(
        architecture="LlamaForCausalLM", vocab_size=256, hidden_size=64,
        num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
        intermediate_size=128, max_position=256)
    cfg = EngineConfig(load_format="dummy", dtype="float32",
                       max_model_len=128,
                       cache=CacheConfig(page_size=4, num_pages=128))
    llm = LLM(config=cfg, model_cfg=model_cfg)
    httpd = serve(llm, "127.0.0.1", 0, served_model="obs-smoke")
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    yield port
    httpd.shutdown()
    httpd.state.engine.shutdown()


def _get(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    conn.request("GET", path)
    r = conn.getresponse()
    body = r.read()
    conn.close()
    return r.status, r.getheader("Content-Type", ""), body


@pytest.mark.obs_smoke
def test_metrics_endpoint_smoke(obs_server):
    port = obs_server
    # drive one real request through the engine so request/step series
    # have samples
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    conn.request("POST", "/v1/completions", body=json.dumps({
        "prompt": [5, 6, 7, 8], "max_tokens": 6, "temperature": 0,
        "ignore_eos": True}),
        headers={"Content-Type": "application/json"})
    r = conn.getresponse()
    assert r.status == 200, r.read()
    r.read()
    conn.close()

    status, ctype, body = _get(port, "/metrics")
    assert status == 200
    assert ctype.startswith("text/plain")
    text = body.decode()
    typed, samples, dupes = parse_exposition(text)
    assert not dupes, f"duplicate samples: {dupes}"
    # every sample must belong to a TYPE-declared metric (histogram
    # samples append _bucket/_sum/_count to the declared name)
    for name, _ in samples:
        base = name
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix) and name[:-len(suffix)] in typed:
                base = name[:-len(suffix)]
                break
        assert base in typed, f"unregistered sample {name}"
    # request-latency histograms carry the request we just ran
    assert samples[("gllm_request_ttft_seconds_count", "")] >= 1
    assert samples[("gllm_request_e2e_seconds_count", "")] >= 1
    # per-step-kind counters: prefill happened; decode steps followed
    assert samples[("gllm_steps_total", '{kind="prefill"}')] >= 1
    step_kinds = {lbl for n, lbl in samples if n == "gllm_steps_total"}
    assert step_kinds >= {'{kind="prefill"}'}
    assert samples[("gllm_decode_steps_total",
                    '{fused="false"}')] >= 1
    # sampler program + shape-signature compile counters moved
    assert samples[("gllm_sampler_program_total",
                    '{program="greedy"}')] >= 1
    assert samples[("gllm_jit_new_shape_signatures_total", "")] >= 1


@pytest.mark.obs_smoke
def test_steptrace_endpoint(obs_server):
    status, _, body = _get(obs_server, "/steptrace")
    assert status == 200
    d = json.loads(body)
    assert d["events"], "steptrace empty after a generate"
    kinds = {e["kind"] for e in d["events"]}
    assert kinds & {"prefill", "decode", "fused_block"}
    assert "by_kind" in d["summary"]
    # incremental dump: since=next_since returns nothing new
    status, _, body = _get(obs_server,
                           f"/steptrace?since={d['next_since']}")
    assert json.loads(body)["events"] == []
