"""Scorer/parser units of the offline eval harnesses (reference
benchmarks/evaluate_bfcl.py + evaluate_mmmu.py drivers)."""

import importlib.util
import os

import pytest


def _load(name):
    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


bfcl = _load("evaluate_bfcl")
mmmu = _load("evaluate_mmmu")


def test_parse_prompt_calls():
    calls = bfcl.parse_prompt_calls(
        "Sure: [get_weather(city='Paris', days=3), noop()]")
    assert calls == [("get_weather", {"city": "Paris", "days": 3}),
                     ("noop", {})]
    assert bfcl.parse_prompt_calls("no calls here") == []
    assert bfcl.parse_prompt_calls("[broken(") == []


def test_parse_native_calls():
    msg = {"tool_calls": [{"function": {
        "name": "f", "arguments": "{\"x\": 1}"}}]}
    assert bfcl.parse_native_calls(msg) == [("f", {"x": 1})]


@pytest.mark.parametrize("calls,expect,irr,want", [
    ([("f", {"a": 1})],
     [{"name": "f", "args": {"a": [1, 2]}, "required": ["a"]}], False, True),
    ([("f", {"a": 3})],
     [{"name": "f", "args": {"a": [1, 2]}, "required": ["a"]}], False, False),
    ([("f", {})],                                   # missing required
     [{"name": "f", "args": {"a": [1]}, "required": ["a"]}], False, False),
    ([("f", {})],                                   # "" ⇒ omittable
     [{"name": "f", "args": {"a": [1, ""]}, "required": ["a"]}], False, True),
    ([("f", {"a": 1, "z": 9})],                     # undeclared arg
     [{"name": "f", "args": {"a": [1]}, "required": ["a"]}], False, False),
    ([], [], True, True),                           # irrelevance detection
    ([("f", {})], [], True, False),
    ([("f", {"a": "PARIS"})],                       # case-folded strings
     [{"name": "f", "args": {"a": ["Paris"]}, "required": ["a"]}],
     False, True),
    ([("g", {"b": 2}), ("f", {"a": 1})],            # order-free parallel
     [{"name": "f", "args": {"a": [1]}, "required": ["a"]},
      {"name": "g", "args": {"b": [2]}, "required": ["b"]}], False, True),
])
def test_bfcl_score(calls, expect, irr, want):
    assert bfcl.score(calls, expect, irr) is want


def test_mmmu_choice_extraction():
    assert mmmu.extract_choice("The answer is B.") == "B"
    assert mmmu.extract_choice(" c") == "C"
    assert mmmu.extract_choice("unclear") is None


def test_parse_prompt_calls_with_leading_prose_brackets():
    calls = bfcl.parse_prompt_calls(
        "[Note] I'll call it now: [get_weather(city='Paris')]")
    assert calls == [("get_weather", {"city": "Paris"})]


def test_extract_choice_ignores_english_words():
    assert mmmu.extract_choice("I think the answer is B") == "B"
    assert mmmu.extract_choice("I cannot see the image") is None
    assert mmmu.extract_choice("A") == "A"
    assert mmmu.extract_choice("(C) because ...") == "C"


def test_extract_choice_a_and_i_phrasings():
    assert mmmu.extract_choice("Option A.") == "A"
    assert mmmu.extract_choice("A is correct") == "A"
    assert mmmu.extract_choice("I would say B") == "B"  # answer-ish verb,
    # but B is the standalone choice mentioned
    assert mmmu.extract_choice("choice (I)") == "I"


# ---- concurrent eval client (VERDICT r03 weak #6) --------------------------

def _stub_server(handler_fn):
    """Tiny threaded HTTP server answering POSTs with handler_fn(path,
    body_dict) -> (status, dict)."""
    import http.server
    import json as _json
    import socketserver
    import threading

    class H(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            n = int(self.headers.get("Content-Length", 0))
            body = _json.loads(self.rfile.read(n) or b"{}")
            status, resp = handler_fn(self.path, body)
            data = _json.dumps(resp).encode()
            self.send_response(status)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

        def log_message(self, *a):
            pass

    class S(socketserver.ThreadingMixIn, http.server.HTTPServer):
        daemon_threads = True

    srv = S(("127.0.0.1", 0), H)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv


def test_post_json_retries_5xx_then_succeeds():
    ec = _load("eval_client")
    calls = []

    def handler(path, body):
        calls.append(path)
        if len(calls) < 3:
            return 503, {"error": "warming up"}
        return 200, {"ok": True, "echo": body["x"]}

    srv = _stub_server(handler)
    try:
        d = ec.post_json("127.0.0.1", srv.server_address[1], "/t",
                         {"x": 7}, retries=3)
        assert d == {"ok": True, "echo": 7}
        assert len(calls) == 3
    finally:
        srv.shutdown()


def test_post_json_4xx_no_retry():
    ec = _load("eval_client")
    calls = []

    def handler(path, body):
        calls.append(1)
        return 400, {"error": "bad"}

    srv = _stub_server(handler)
    try:
        with pytest.raises(RuntimeError):
            ec.post_json("127.0.0.1", srv.server_address[1], "/t", {},
                         retries=3)
        assert len(calls) == 1, "4xx must not be retried"
    finally:
        srv.shutdown()


def test_mmlu_pro_concurrent_run(tmp_path, capsys, monkeypatch):
    """The harness drives N questions concurrently against a stub server
    and scores the canned answers."""
    import json as _json
    import threading

    data = tmp_path / "q.jsonl"
    qs = [{"question": f"q{i}", "options": ["x", "y", "z"],
           "answer": i % 3} for i in range(20)]
    data.write_text("\n".join(_json.dumps(q) for q in qs))

    seen = set()
    lock = threading.Lock()

    def handler(path, body):
        q = body["messages"][0]["content"]
        i = int(q.split("q", 1)[1].split("\n", 1)[0])
        with lock:
            seen.add(i)
        return 200, {"choices": [{"message":
                                  {"content": f"Answer: {'ABC'[i % 3]}"}}]}

    srv = _stub_server(handler)
    try:
        mm = _load("evaluate_mmlu_pro")
        monkeypatch.setattr("sys.argv", [
            "evaluate_mmlu_pro.py", "--data-path", str(data),
            "--port", str(srv.server_address[1]), "--concurrency", "8"])
        mm.main()
    finally:
        srv.shutdown()
    out = [ln for ln in capsys.readouterr().out.splitlines()
           if ln.startswith("{")]
    d = _json.loads(out[-1])
    assert d["metric"] == "mmlu_pro_accuracy"
    assert d["value"] == 1.0 and d["n"] == 20
    assert seen == set(range(20))


def test_serve_bench_summary_and_poisson(tmp_path, capsys, monkeypatch):
    """serve_bench drives a streaming stub server with poisson arrivals
    and reports the full latency distribution shape."""
    import http.server
    import json as _json
    import socketserver
    import threading
    import time as _time

    class H(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            n = int(self.headers.get("Content-Length", 0))
            self.rfile.read(n)
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.end_headers()
            for i in range(4):
                ev = {"choices": [{"index": 0, "text": f"t{i}",
                                   "finish_reason": None}]}
                self.wfile.write(b"data: " + _json.dumps(ev).encode()
                                 + b"\n\n")
                self.wfile.flush()
                _time.sleep(0.01)
            self.wfile.write(b"data: [DONE]\n\n")

        def log_message(self, *a):
            pass

    class S(socketserver.ThreadingMixIn, http.server.HTTPServer):
        daemon_threads = True

    srv = S(("127.0.0.1", 0), H)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        sb = _load("serve_bench")
        monkeypatch.setattr("sys.argv", [
            "serve_bench.py", "--port", str(srv.server_address[1]),
            "--num-prompts", "6", "--concurrency", "3",
            "--prompt-len", "16", "--output-len", "4",
            "--request-rate", "50"])
        sb.main()
    finally:
        srv.shutdown()
    out = capsys.readouterr().out
    d = _json.loads(out)
    assert d["completed"] == 6 and d["failed"] == 0
    assert d["output_tokens"] == 24
    for k in ("ttft_ms", "tpot_ms", "itl_ms", "e2e_ms"):
        assert set(d[k]) == {"mean", "p50", "p90", "p99"}, d[k]
    assert d["e2e_ms"]["p50"] > 0
    # events arrive INCREMENTALLY (read1-based client): the stub staggers
    # chunks 10 ms apart, so SOME nonzero inter-arrival must be observed —
    # the old blocking read(4096) batched every event into one read and
    # reported exactly 0 (regression: it faked TTFT/ITL until r5). A
    # loaded CI box may coalesce some intervals, so only >0 is asserted.
    assert d["itl_ms"]["mean"] > 0, d["itl_ms"]


def test_bfcl_native_mode_qwen35_xml_chain():
    """BFCL native mode over the Qwen3.5 XML markup: model output →
    Qwen3XmlToolParser (schema coercion) → OpenAI message shape →
    bfcl.parse_native_calls → AST scorer. Proves the whole native-mode
    chain the reference exercises with its qwen3 parser
    (tool_parsers.py:346-425)."""
    from gllm_tpu.entrypoints.tool_parsers import (Qwen3XmlToolParser,
                                                   schemas_from_tools)
    tools = [{"type": "function", "function": {
        "name": "get_weather", "parameters": {
            "properties": {"city": {"type": "string"},
                           "days": {"type": "integer"}}}}}]
    model_out = ("<tool_call>\n<function=get_weather>\n"
                 "<parameter=city>\nParis\n</parameter>\n"
                 "<parameter=days>\n3\n</parameter>\n"
                 "</function>\n</tool_call>")
    _, calls = Qwen3XmlToolParser().parse(model_out,
                                          schemas_from_tools(tools))
    message = {"tool_calls": [c.to_openai() for c in calls]}
    parsed = bfcl.parse_native_calls(message)
    assert parsed == [("get_weather", {"city": "Paris", "days": 3})]
    assert bfcl.score(
        parsed,
        [{"name": "get_weather",
          "args": {"city": ["Paris"], "days": [3]},
          "required": ["city", "days"]}], False) is True
