"""API server tests: real HTTP requests against a live threaded server."""

import http.client
import json
import threading

import pytest
import torch

from gllm_tpu.config import CacheConfig, EngineConfig
from gllm_tpu.engine.llm import LLM
from gllm_tpu.entrypoints.api_server import serve


class StubTokenizer:
    """Minimal word-level tokenizer: token id = byte value of 1-char words,
    good enough to drive encode/decode/chat-template paths."""
    eos_token_id = 0

    def encode(self, text):
        return [min(ord(c), 120) for c in text][:64]

    def decode(self, ids, skip_special_tokens=False):
        return "".join(chr(max(32, i % 127)) for i in ids)

    def apply_chat_template(self, messages, add_generation_prompt=True,
                            **kw):
        text = " ".join(str(m.get("content", "")) for m in messages)
        return self.encode(text or "hi")


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    from transformers import LlamaConfig, LlamaForCausalLM
    torch.manual_seed(2)
    d = tmp_path_factory.mktemp("srv_model")
    LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=96,
        max_position_embeddings=256, eos_token_id=0,
        attention_bias=False)).save_pretrained(d, safe_serialization=True)
    cfg = EngineConfig(model=str(d), dtype="float32", max_model_len=128,
                       cache=CacheConfig(page_size=4, num_pages=128))
    llm = LLM(config=cfg, tokenizer=StubTokenizer())
    httpd = serve(llm, "127.0.0.1", 0)
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    yield port
    httpd.shutdown()
    httpd.state.engine.shutdown()


def request(port, method, path, body=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    conn.request(method, path,
                 body=json.dumps(body) if body is not None else None,
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, data


def test_health_version_models(server):
    status, body = request(server, "GET", "/health")
    assert status == 200 and json.loads(body)["status"] == "ok"
    status, body = request(server, "GET", "/version")
    assert status == 200 and "version" in json.loads(body)
    status, body = request(server, "GET", "/v1/models")
    assert json.loads(body)["data"][0]["object"] == "model"
    status, body = request(server, "GET", "/server_info")
    info = json.loads(body)
    assert info["page_size"] == 4 and info["parallel"]["tp"] == 1


def test_completion_token_array(server):
    status, body = request(server, "POST", "/v1/completions", {
        "prompt": [5, 17, 93], "max_tokens": 6, "temperature": 0,
        "ignore_eos": True})
    assert status == 200, body
    d = json.loads(body)
    assert d["object"] == "text_completion"
    assert d["usage"] == {"prompt_tokens": 3, "completion_tokens": 6,
                          "total_tokens": 9}
    assert d["choices"][0]["finish_reason"] == "length"
    assert len(d["choices"][0]["text"]) > 0


def test_completion_text_prompt(server):
    status, body = request(server, "POST", "/v1/completions", {
        "prompt": "hello", "max_tokens": 4, "temperature": 0})
    assert status == 200, body
    assert json.loads(body)["choices"][0]["text"] is not None


def test_chat_completion(server):
    status, body = request(server, "POST", "/v1/chat/completions", {
        "messages": [{"role": "user", "content": "hey"}],
        "max_tokens": 5, "temperature": 0, "ignore_eos": True})
    assert status == 200, body
    d = json.loads(body)
    assert d["object"] == "chat.completion"
    assert d["choices"][0]["message"]["role"] == "assistant"
    assert d["usage"]["completion_tokens"] == 5


def test_chat_streaming_sse(server):
    conn = http.client.HTTPConnection("127.0.0.1", server, timeout=60)
    conn.request("POST", "/v1/chat/completions", body=json.dumps({
        "messages": [{"role": "user", "content": "stream me"}],
        "max_tokens": 5, "temperature": 0, "stream": True,
        "ignore_eos": True}),
        headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200
    assert resp.getheader("Content-Type").startswith("text/event-stream")
    raw = resp.read().decode()
    conn.close()
    events = [line[6:] for line in raw.split("\n\n")
              if line.startswith("data: ")]
    assert events[-1] == "[DONE]"
    chunks = [json.loads(e) for e in events[:-1]]
    assert chunks[0]["choices"][0]["delta"].get("role") == "assistant"
    finals = [c for c in chunks
              if c["choices"][0]["finish_reason"] is not None]
    assert finals and finals[-1]["choices"][0]["finish_reason"] == "length"
    deltas = "".join(c["choices"][0]["delta"].get("content", "")
                     for c in chunks)
    assert len(deltas) > 0


def test_chat_streaming_n2(server):
    """stream=true with n=2: one SSE stream, per-choice indices, both
    choices finish (VERDICT r2 parity closure)."""
    conn = http.client.HTTPConnection("127.0.0.1", server, timeout=120)
    conn.request("POST", "/v1/chat/completions", body=json.dumps({
        "messages": [{"role": "user", "content": "двое"}],
        "max_tokens": 4, "temperature": 0, "stream": True, "n": 2,
        "ignore_eos": True}),
        headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200, resp.read()
    raw = resp.read().decode()
    conn.close()
    events = [line[6:] for line in raw.split("\n\n")
              if line.startswith("data: ")]
    assert events[-1] == "[DONE]"
    chunks = [json.loads(e) for e in events[:-1]]
    by_idx = {}
    for c in chunks:
        ch = c["choices"][0]
        by_idx.setdefault(ch["index"], []).append(ch)
    assert set(by_idx) == {0, 1}
    for i in (0, 1):
        assert by_idx[i][0]["delta"].get("role") == "assistant"
        assert any(ch["finish_reason"] == "length" for ch in by_idx[i])
        text = "".join(ch["delta"].get("content", "") for ch in by_idx[i])
        assert len(text) > 0
    # greedy decoding → both choices produce identical text
    t0 = "".join(ch["delta"].get("content", "") for ch in by_idx[0])
    t1 = "".join(ch["delta"].get("content", "") for ch in by_idx[1])
    assert t0 == t1


def test_completion_streaming_n2(server):
    conn = http.client.HTTPConnection("127.0.0.1", server, timeout=120)
    conn.request("POST", "/v1/completions", body=json.dumps({
        "prompt": [5, 17, 93], "max_tokens": 4, "temperature": 0,
        "stream": True, "n": 2, "ignore_eos": True}),
        headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200
    raw = resp.read().decode()
    conn.close()
    events = [line[6:] for line in raw.split("\n\n")
              if line.startswith("data: ")]
    assert events[-1] == "[DONE]"
    idxs = {json.loads(e)["choices"][0]["index"] for e in events[:-1]}
    assert idxs == {0, 1}


def test_concurrent_requests(server):
    results = []

    def one(i):
        status, body = request(server, "POST", "/v1/completions", {
            "prompt": [3 + i, 8, 1], "max_tokens": 6, "temperature": 0,
            "ignore_eos": True})
        results.append((status, json.loads(body)))

    threads = [threading.Thread(target=one, args=(i,)) for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(results) == 6
    assert all(s == 200 for s, _ in results)
    assert all(d["usage"]["completion_tokens"] == 6 for _, d in results)


def test_bad_requests(server):
    status, body = request(server, "POST", "/v1/chat/completions",
                           {"messages": []})
    assert status == 400
    assert "error" in json.loads(body)
    status, body = request(server, "POST", "/v1/completions",
                           {"prompt": 42})
    assert status == 400
    status, body = request(server, "POST", "/v1/completions",
                           {"prompt": "x", "temperature": -2})
    assert status == 400
    status, _ = request(server, "POST", "/v1/unknown", {})
    assert status == 404


def test_chat_streaming_with_tools(server):
    """Streamed chat WITH tools rides the incremental StreamingToolCalls
    path: text deltas arrive live (multiple SSE events) even when no tool
    markup is generated."""
    conn = http.client.HTTPConnection("127.0.0.1", server, timeout=60)
    conn.request("POST", "/v1/chat/completions", body=json.dumps({
        "messages": [{"role": "user", "content": "call a tool"}],
        "max_tokens": 6, "temperature": 0, "stream": True,
        "ignore_eos": True,
        "tools": [{"type": "function", "function": {
            "name": "noop", "parameters": {"type": "object",
                                           "properties": {}}}}]}),
        headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.getheader("Content-Type").startswith("text/event-stream")
    events = []
    for line in resp.read().decode().splitlines():
        if line.startswith("data: ") and line != "data: [DONE]":
            events.append(json.loads(line[6:]))
    conn.close()
    deltas = [e["choices"][0]["delta"] for e in events]
    # role preamble + per-token content deltas + finish chunk
    assert deltas[0].get("role") == "assistant"
    content = "".join(d.get("content") or "" for d in deltas)
    assert len(content) > 0
    assert sum(1 for d in deltas if d.get("content")) >= 2, \
        "content must stream incrementally, not as one buffered delta"
    fins = [e["choices"][0].get("finish_reason") for e in events]
    assert fins[-1] == "length"


def test_completion_min_p_and_logit_bias(server):
    """min_p + logit_bias accepted on completions; a +100 bias provably
    forces every sampled token (VERDICT r03 missing #2)."""
    status, body = request(server, "POST", "/v1/completions", {
        "prompt": [5, 17, 93], "max_tokens": 4, "temperature": 0,
        "ignore_eos": True, "min_p": 0.1, "logit_bias": {"65": 100.0}})
    assert status == 200, body
    # StubTokenizer decodes token 65 -> "A"
    assert json.loads(body)["choices"][0]["text"] == "AAAA"
    status, body = request(server, "POST", "/v1/completions", {
        "prompt": [5], "max_tokens": 2, "logit_bias": {"65": 200.0}})
    assert status == 400


def test_chat_min_p_and_logit_bias(server):
    status, body = request(server, "POST", "/v1/chat/completions", {
        "messages": [{"role": "user", "content": "hey"}],
        "max_tokens": 4, "temperature": 0, "ignore_eos": True,
        "min_p": 0.05, "logit_bias": {"66": 100.0}})
    assert status == 200, body
    assert json.loads(body)["choices"][0]["message"]["content"] == "BBBB"
    status, body = request(server, "POST", "/v1/chat/completions", {
        "messages": [{"role": "user", "content": "hey"}],
        "max_tokens": 2, "min_p": -0.5})
    assert status == 400


def test_metrics_exposition_after_generate(server):
    """GET /metrics returns valid Prometheus text exposition carrying
    request-latency histograms (TTFT/TPOT/e2e) and per-step-kind
    counters once a generate has run."""
    from gllm_tpu.obs.metrics import parse_exposition

    status, body = request(server, "POST", "/v1/completions", {
        "prompt": [9, 8, 7], "max_tokens": 5, "temperature": 0,
        "ignore_eos": True})
    assert status == 200, body

    conn = http.client.HTTPConnection("127.0.0.1", server, timeout=60)
    conn.request("GET", "/metrics")
    resp = conn.getresponse()
    ctype = resp.getheader("Content-Type", "")
    text = resp.read().decode()
    conn.close()
    assert resp.status == 200 and ctype.startswith("text/plain")

    typed, samples, dupes = parse_exposition(text)
    assert not dupes
    for name in ("gllm_request_ttft_seconds",
                 "gllm_request_tpot_seconds",
                 "gllm_request_e2e_seconds"):
        assert typed.get(name) == "histogram", name
    assert samples[("gllm_request_ttft_seconds_count", "")] >= 1
    assert samples[("gllm_request_e2e_seconds_count", "")] >= 1
    assert samples[("gllm_steps_total", '{kind="prefill"}')] >= 1
    assert samples[("gllm_decode_steps_total", '{fused="false"}')] >= 1
    assert samples[("gllm_requests_submitted_total", "")] >= 1


def test_steptrace_endpoint_after_generate(server):
    status, body = request(server, "POST", "/v1/completions", {
        "prompt": [4, 4, 4], "max_tokens": 3, "temperature": 0,
        "ignore_eos": True})
    assert status == 200, body
    conn = http.client.HTTPConnection("127.0.0.1", server, timeout=60)
    conn.request("GET", "/steptrace")
    resp = conn.getresponse()
    d = json.loads(resp.read())
    conn.close()
    assert resp.status == 200
    assert d["events"] and "by_kind" in d["summary"]
    assert {e["kind"] for e in d["events"]} & {"prefill", "decode",
                                              "fused_block"}


def test_server_info_advertises_topology_and_fast_path(server):
    """/server_info carries the full topology story (ISSUE 20): the
    pp/dp/tp grid, the per-stage layer assignment (None on the
    single-runner), and which fast-path flags this topology runs."""
    status, body = request(server, "GET", "/server_info")
    info = json.loads(body)
    par = info["parallel"]
    assert (par["pp"], par["dp"], par["tp"]) == (1, 1, 1)
    assert par["stage_layers"] is None
    assert set(par["fast_path"]) == {"overlap_scheduling",
                                     "pipelined_loop", "spec_fused"}
    # the device as jax reports it (chip_smoke.py's jax-free parent
    # reads its verdict's device block from here)
    assert info["device"] == {"platform": "cpu", "kind": "cpu",
                              "count": 8, "memory": [None] * 8}


@pytest.mark.slow   # builds a real pp=2 engine behind a live HTTP server
def test_server_info_pp_stage_layers(tmp_path):
    """A pp=2 server advertises each stage's [first, last) layer block
    and the lifted fast-path flags it actually runs."""
    from transformers import LlamaConfig, LlamaForCausalLM
    from gllm_tpu.config import ParallelConfig
    torch.manual_seed(3)
    LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=64, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=96, max_position_embeddings=256,
        eos_token_id=0, attention_bias=False)).save_pretrained(
            tmp_path, safe_serialization=True)
    cfg = EngineConfig(
        model=str(tmp_path), dtype="float32", max_model_len=128,
        overlap_scheduling=True, pipelined_loop=True,
        cache=CacheConfig(page_size=4, num_pages=128),
        parallel=ParallelConfig(pp=2))
    llm = LLM(config=cfg, tokenizer=StubTokenizer())
    httpd = serve(llm, "127.0.0.1", 0)
    port = httpd.server_address[1]
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        status, body = request(port, "GET", "/server_info")
        info = json.loads(body)
        par = info["parallel"]
        assert par["pp"] == 2
        assert par["stage_layers"] == [[0, 2], [2, 4]]
        fp = par["fast_path"]
        assert fp["overlap_scheduling"] and fp["pipelined_loop"]
        assert not fp["spec_fused"]
    finally:
        httpd.shutdown()
        httpd.state.engine.shutdown()
