#!/usr/bin/env python3
"""Chip smoke: the serving path, end to end, on the attached TPU.

    python chip_smoke.py              # one chip: serve, serve-ref, serve-fast
    python chip_smoke.py --chips 4    # four chips: tp4-pallas, tp4-xla, pp4
    python chip_smoke.py --cpu-rehearsal [--chips 4]   # tiny model, CPU

Each arm is one ``python -m gllm_tpu.entrypoints.api_server`` child that
owns the chip(s); arms run one after another. This parent uses the standard
library only and never imports jax or gllm_tpu: a chip belongs to one
process at a time, and a parent that touched jax would hold it.

The model is seeded random weights (``--load-format dummy``) at full
published widths and depth; only a ``config.json`` is written, under
``chiprun_out/chip_smoke/``. With random weights any garbage looks
plausible, so answers are compared, not only received:

- prefill: per-position prompt logprobs of one long prompt (longer than
  ``--maxp``, so chunked prefill runs) from each Pallas arm against the
  XLA-attention arm;
- decode: the logprobs reported while decoding against the logprobs of the
  same tokens when prompt+output is replayed as a prompt (decode kernel +
  KV write against the prefill kernel), and for requests that ran without
  logprobs (the fused blocks) how far each greedy token is from the
  replay's top-1.

The last line of stdout is one JSON object: ``{"ok": true, "device":
{"platform": "tpu", "kind": ..., "count": N}}`` with the device as the
serving child's jax reported it, or ``{"ok": false, ...}`` and a non-zero
exit code. Without a TPU it fails; it never carries on on the CPU unless
``--cpu-rehearsal`` asks for that, and then it says so and cannot print
``"platform": "tpu"``.
"""

import argparse
import json
import math
import os
import random
import signal
import socket
import statistics
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "chiprun_out", "chip_smoke")
SEED = 0

# Llama-3.2-1B widths at full depth. A smoke model, not a benchmark cell
# (ROADMAP keeps the family out of cells).
MODEL_1CHIP = {
    "architectures": ["LlamaForCausalLM"], "vocab_size": 128256,
    "hidden_size": 2048, "num_hidden_layers": 16,
    "num_attention_heads": 32, "num_key_value_heads": 8, "head_dim": 64,
    "intermediate_size": 8192, "max_position_embeddings": 4096,
    "rope_theta": 500000.0, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": True, "eos_token_id": 128001}
# Qwen3-8B widths at full depth: 16 GB of bf16 weights do not fit one
# 16 GB chip, and head_dim 128 lets the Pallas kernels run under the tp
# shard_map.
MODEL_4CHIP = {
    "architectures": ["Qwen3ForCausalLM"], "vocab_size": 151936,
    "hidden_size": 4096, "num_hidden_layers": 36,
    "num_attention_heads": 32, "num_key_value_heads": 8, "head_dim": 128,
    "intermediate_size": 12288, "max_position_embeddings": 4096,
    "rope_theta": 1000000.0, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False, "eos_token_id": 151645}
# --cpu-rehearsal: the same architecture and control flow at widths the
# CPU (and the Pallas interpreter) can serve.
TINY_WIDTHS = {
    "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "intermediate_size": 128, "max_position_embeddings": 512,
    "eos_token_id": 1}

FAST_FLAGS = ["--overlap-scheduling", "--pipelined-loop",
              "--decode-slot-batching", "--ondevice-finish",
              "--decode-chain-len", "16",
              "--spec-decode", "ngram", "--spec-fused"]
# what the fast arm's steps are: prompts, fused decode chains, and the
# single decode steps where a chain could not be extended
FAST_KINDS = ("prefill", "decode", "fused_block")

# The bf16 tolerance, settled after seeing the chip's numbers (CHANGES.md,
# PR 21). It is relative to the SPREAD of the reference logprobs (their
# standard deviation over the long prompt's positions), because the two
# smoke models differ 40x in logit scale: the 1B model ties its head to a
# unit-variance embedding (logit std ~ sqrt(2048) ~ 45, logprobs of random
# tokens ~ -200 +- 60), the 8B model's separate head gives logit std ~ 1.
# Rounding to bf16 after every layer leaves the hidden state ~2% off
# between two correct attention paths, which moves a logprob by a few
# percent of that spread; a wrong kernel moves it by the spread itself.
TOL_REL = 0.15
TOP1_AGREE = 0.5    # share of positions where two arms pick the same top-1


class SmokeFailure(Exception):
    pass


def log(msg):
    print(msg, flush=True)


def check(cond, what):
    if not cond:
        raise SmokeFailure(what)


# ---- HTTP (stdlib) ---------------------------------------------------------

def http(port, method, path, body=None, timeout=600):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=data, method=method,
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def get_json(port, path, timeout=60):
    status, body = http(port, "GET", path, timeout=timeout)
    check(status == 200, f"GET {path} -> {status}: {body[:300]!r}")
    return json.loads(body)


def metric_samples(text, name):
    """{label-string: value} of one Prometheus metric's samples."""
    out = {}
    for line in text.splitlines():
        if line.startswith(name) and line[len(name)] in " {":
            head, value = line.rsplit(" ", 1)
            out[head[len(name):]] = float(value)
    return out


def completion(port, prompt, max_tokens, *, stream=False, **extra):
    """One /v1/completions call on a token-id prompt. Returns (finish
    reason, number of output tokens, logprobs dict or None); a stream
    returns its token ids in the third place (the per-token ids the front
    router journals — the only way to see tokens without a tokenizer and
    without asking for logprobs, which would keep a request off the fused
    path)."""
    body = dict(model="smoke", prompt=prompt, max_tokens=max_tokens,
                ignore_eos=True, stream=stream, **extra)
    if not stream:
        status, raw = http(port, "POST", "/v1/completions", body)
        check(status == 200, f"completion -> {status}: {raw[:300]!r}")
        out = json.loads(raw)
        choice = out["choices"][0]
        return (choice["finish_reason"],
                out["usage"]["completion_tokens"], choice.get("logprobs"))
    body["gllm_router"] = {}
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/completions",
        data=json.dumps(body).encode(), method="POST",
        headers={"Content-Type": "application/json"})
    ids, finish = [], None
    with urllib.request.urlopen(req, timeout=600) as r:
        check(r.status == 200, f"stream -> {r.status}")
        for line in r:
            line = line.strip()
            if not line.startswith(b"data: ") or line == b"data: [DONE]":
                continue
            ev = json.loads(line[6:])
            check("error" not in ev, f"stream error event: {ev}")
            if "choices" not in ev:
                continue     # the router preamble
            ids.append(ev["gllm"]["token_id"])   # one event per token
            finish = ev["choices"][0].get("finish_reason") or finish
    return finish, len(ids), ids


# ---- the serving child -----------------------------------------------------

class Server:
    """One api_server child: start, wait for /readyz, SIGTERM, exit 0."""

    def __init__(self, name, model_dir, flags, env, ready_timeout):
        self.name, self.model_dir, self.flags = name, model_dir, flags
        self.env, self.ready_timeout = env, ready_timeout
        self.log_path = os.path.join(OUT, f"{name}.log")
        self.proc = None
        self.startup_s = None
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]

    def __enter__(self):
        cmd = [sys.executable, "-m", "gllm_tpu.entrypoints.api_server",
               "--model", self.model_dir, "--tokenizer", "",
               "--load-format", "dummy", "--seed", str(SEED),
               "--host", "127.0.0.1", "--port", str(self.port)] + self.flags
        log(f"[{self.name}] $ " + " ".join(cmd[1:]))
        t0 = time.monotonic()
        self.log_file = open(self.log_path, "w")
        self.proc = subprocess.Popen(cmd, cwd=HERE, env=self.env,
                                     stdout=self.log_file,
                                     stderr=subprocess.STDOUT)
        try:
            while True:
                if self.proc.poll() is not None:
                    raise SmokeFailure(
                        f"{self.name}: server exited with code "
                        f"{self.proc.returncode} before it was ready")
                if time.monotonic() - t0 > self.ready_timeout:
                    raise SmokeFailure(
                        f"{self.name}: not ready after "
                        f"{self.ready_timeout:.0f}s")
                try:
                    status, _ = http(self.port, "GET", "/readyz", timeout=5)
                    if status == 200:
                        break
                except (urllib.error.URLError, OSError):
                    pass
                time.sleep(1.0)
        except BaseException:
            self._stop()
            self._tail()
            raise
        self.startup_s = time.monotonic() - t0
        return self

    def _stop(self):
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=90)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self.log_file.close()
        return self.proc.returncode

    def _tail(self, n=40):
        with open(self.log_path, errors="replace") as f:
            lines = f.readlines()[-n:]
        log(f"[{self.name}] --- last {len(lines)} log lines "
            f"({self.log_path}) ---")
        for line in lines:
            log("  | " + line.rstrip())

    def __exit__(self, exc_type, exc, tb):
        rc = self._stop()
        if exc_type is not None or rc != 0:
            self._tail()
        if exc_type is None:
            check(rc == 0, f"{self.name}: server exit code {rc} after "
                           "SIGTERM (want 0)")
        return False


# ---- traffic ---------------------------------------------------------------

def finite_logprobs(lp, where):
    vals = [v for v in lp["token_logprobs"] if v is not None]
    check(vals and all(math.isfinite(v) for v in vals),
          f"{where}: non-finite logprob")
    return lp


def prompt_scores(port, tokens):
    """By echoing ``tokens`` as a prompt, for positions 1..n-1: the logprob
    of each given token, and the top-1 logprob and token there."""
    fin, n_out, lp = completion(port, tokens, 1, temperature=0.0,
                                echo=True, prompt_logprobs=1, logprobs=1)
    check(fin == "length" and n_out == 1, f"echo request: {fin}/{n_out}")
    finite_logprobs(lp, "prompt logprobs")
    n = len(tokens)
    check(len(lp["token_logprobs"]) == n + 1,
          f"echo returned {len(lp['token_logprobs'])} entries for "
          f"{n} prompt tokens + 1")
    top = [next(iter(t.items())) for t in lp["top_logprobs"][1:n]]
    return {"given": lp["token_logprobs"][1:n],
            "top1": [v for _, v in top], "top1_id": [k for k, _ in top]}


def drive(port, size, rng_tokens):
    """The request plan of one arm. Returns what the comparisons need."""
    res = {"tokens": 0}
    n_short, n_out = size["short_prompt"], size["decode_tokens"]

    # 1. greedy decode WITH logprobs (the plain decode path), then the
    #    same tokens replayed as a prompt
    p1 = rng_tokens(n_short)
    fin, n, lp = completion(port, p1, n_out, temperature=0.0, logprobs=1)
    check(fin == "length" and n == n_out, f"decode request: {fin}/{n}")
    finite_logprobs(lp, "decode logprobs")
    o1 = [int(t) for t in lp["tokens"]]
    replay = prompt_scores(port, p1 + o1)
    res["decode_lp"] = lp["token_logprobs"]
    res["decode_vs_replay"] = max(
        abs(a - b) for a, b in zip(lp["token_logprobs"],
                                   replay["given"][len(p1) - 1:]))
    res["tokens"] += n + 1

    # 2. the long prompt (> --maxp: chunked prefill), per-position
    #    logprobs for the cross-arm prefill comparison
    long_prompt = rng_tokens(size["long_prompt"])
    res["long"] = prompt_scores(port, long_prompt)
    res["spread"] = statistics.pstdev(res["long"]["given"])
    res["tokens"] += 1

    # 3. a few at once, no logprobs (so the fast path may chain and fuse):
    #    greedy and temperature 0.7 / top-p 0.95, streamed and not
    jobs = []
    for i in range(size["concurrent"]):
        sampled = size["sampled"] and i % 2 == 1
        kw = (dict(temperature=0.7, top_p=0.95) if sampled
              else dict(temperature=0.0))
        jobs.append(dict(prompt=rng_tokens(n_short - 4 + i),
                         max_tokens=n_out + 8, stream=i % 3 == 0, kw=kw))
    outs = [None] * len(jobs)

    def run(i, job):
        try:
            outs[i] = completion(port, job["prompt"], job["max_tokens"],
                                 stream=job["stream"], **job["kw"])
        except Exception as e:      # surfaced below, in the main thread
            outs[i] = e

    threads = [threading.Thread(target=run, args=(i, j))
               for i, j in enumerate(jobs)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=900)
    for job, out in zip(jobs, outs):
        check(out is not None, "concurrent request did not return")
        if isinstance(out, Exception):
            raise SmokeFailure(f"concurrent request failed: {out!r}")
        fin, n, _ = out
        check(fin == "length" and n == job["max_tokens"],
              f"concurrent request: {fin}/{n} (want length/"
              f"{job['max_tokens']})")
        res["tokens"] += n

    # job 0 is greedy and streamed: decoded in a batch, without logprobs
    # (inside fused blocks in the fast arm). Replayed as a prompt, every
    # one of its tokens must be the replay's top-1 up to a bf16 near-tie.
    p0, o0 = jobs[0]["prompt"], outs[0][2]
    replay = prompt_scores(port, p0 + o0)
    res["greedy_gap"] = max(
        t - g for g, t in zip(replay["given"][len(p0) - 1:],
                              replay["top1"][len(p0) - 1:]))
    res["tokens"] += 1
    return res


# ---- one arm ---------------------------------------------------------------

def run_arm(name, model_dir, flags, env, size, want, ready_timeout):
    """Start one server, drive it, read its counters, stop it. ``want``:
    platform, attention_impl, and optional extra checks."""
    rng = random.Random(SEED)
    vocab = size["vocab"]
    rng_tokens = lambda n: [rng.randrange(2, vocab) for _ in range(n)]
    with Server(name, model_dir, flags, env, ready_timeout) as srv:
        port = srv.port
        info = get_json(port, "/server_info")
        dev = info["device"]
        check(dev["platform"] == want["platform"],
              f"{name}: platform is {dev['platform']!r}, want "
              f"{want['platform']!r}")
        check(dev["count"] == want["count"],
              f"{name}: {dev['count']} devices, want {want['count']}")
        if want.get("attention_impl"):
            check(info["attention_impl"] == want["attention_impl"],
                  f"{name}: attention_impl is {info['attention_impl']!r}, "
                  f"want {want['attention_impl']!r}")
        mark = get_json(port, "/steptrace?kind=compile")["next_since"]
        xla0 = metric_samples(http(port, "GET", "/metrics")[1].decode(),
                              "gllm_xla_programs_total")
        res = drive(port, size, rng_tokens)
        if want.get("profile"):
            # a window of its own, with traffic in it
            profile = threading.Thread(
                target=lambda: res.__setitem__("profile_dir", json.loads(
                    http(port, "POST", "/profile?seconds=1",
                         timeout=120)[1]).get("trace_dir")))
            profile.start()
            completion(port, rng_tokens(size["short_prompt"]),
                       size["decode_tokens"], temperature=0.0)
            profile.join(timeout=180)

        trace = get_json(port, f"/steptrace?since={mark}", timeout=120)
        summ = trace["summary"]
        check(summ["quarantines"] == 0 and summ["faults"] == 0,
              f"{name}: quarantines={summ['quarantines']} "
              f"faults={summ['faults']} (a failed step was isolated)")
        metrics = http(port, "GET", "/metrics")[1].decode()
        steps = {k.strip('{}').split('"')[1]: int(v) for k, v in
                 metric_samples(metrics, "gllm_steps_total").items()}
        xla_n = metric_samples(metrics, "gllm_xla_programs_total")
        xla_s = metric_samples(metrics, "gllm_xla_compile_seconds_total")
        compiled = xla_n.get('{source="compiled"}', 0)
        cached = xla_n.get('{source="cache"}', 0)
        info = get_json(port, "/server_info")
        mem = info["device"]["memory"]
        res.update(name=name, device=dev, steps=steps, info=info,
                   trace_events=trace["events"], cached=cached)
        log(f"[{name}] startup_s={srv.startup_s:.1f} "
            f"compile_s={sum(xla_s.values()):.1f} "
            f"programs_compiled={compiled:.0f} "
            f"programs_from_cache={cached:.0f} "
            f"(before requests: {sum(xla0.values()):.0f}) "
            f"compile_events_in_request_window={summ['compiles']} "
            f"kv_pages={info['num_pages']} tokens={res['tokens']} "
            f"steps={steps}")
        for e in trace["events"]:
            if e.get("kind") == "compile":
                log(f"[{name}]   new shape in request window: "
                    f"{e.get('dispatch')} tokens={e.get('tokens_pad')} "
                    f"seqs={e.get('seqs_pad')} pages={e.get('pages_pad')} "
                    f"flags={e.get('flags')}")
        for i, m in enumerate(mem):
            if m:
                log(f"[{name}] device {i}: limit={m['bytes_limit']} "
                    f"in_use={m['bytes_in_use']} "
                    f"peak={m['peak_bytes_in_use']}")
        tol = TOL_REL * res["spread"]
        dlp = res["decode_lp"]
        log(f"[{name}] logprob spread over the long prompt="
            f"{res['spread']:.4f} (tolerance {TOL_REL} x spread = "
            f"{tol:.4f}); decode logprobs min={min(dlp):.4f} "
            f"max={max(dlp):.4f}")
        log(f"[{name}] decode_vs_replay_max_abs={res['decode_vs_replay']:.6f}"
            f" greedy_top1_gap_max={res['greedy_gap']:.6f}")
        check(res["decode_vs_replay"] <= tol,
              f"{name}: decode logprobs differ from replayed-as-prompt "
              f"logprobs by {res['decode_vs_replay']:.4f} > {tol:.4f}")
        check(res["greedy_gap"] <= tol,
              f"{name}: a greedy token is {res['greedy_gap']:.4f} below "
              f"the replay's top-1 (> {tol:.4f})")
    log(f"[{name}] server stopped with exit code 0")
    return res


def compare_prefill(arm, ref, gate=True):
    """Per-position prompt logprobs of the long prompt, ``arm`` against
    ``ref``; ``gate=False`` only prints (a second sample of the noise)."""
    a, b = arm["long"], ref["long"]
    diffs = [abs(x - y) for x, y in zip(a["given"], b["given"])]
    n = len(diffs)
    worst = max(range(n), key=diffs.__getitem__)
    ranked = sorted(diffs)
    agree = sum(x == y for x, y in zip(a["top1_id"], b["top1_id"])) / n
    tol = TOL_REL * ref["spread"]
    log(f"[{arm['name']}] prefill_vs_{ref['name']}: max_abs={ranked[-1]:.4f}"
        f" (position {worst + 1} of {n}; there {a['given'][worst]:.2f} vs "
        f"{b['given'][worst]:.2f}) p99={ranked[int(0.99 * n)]:.4f} "
        f"median={ranked[n // 2]:.4f} top1_agreement={agree:.4f} "
        f"tolerance={tol:.4f}")
    if gate:
        check(ranked[-1] <= tol,
              f"{arm['name']}: prompt logprobs differ from {ref['name']} "
              f"by {ranked[-1]:.4f} > {tol:.4f}")
        check(agree >= TOP1_AGREE,
              f"{arm['name']}: top-1 tokens agree with {ref['name']} at "
              f"only {agree:.2%} of positions")


def plane_names(trace_dir, env):
    """Plane names of the newest .xplane.pb under ``trace_dir``, read by a
    CPU-only child after the server has gone (A1 needs the device plane)."""
    code = (
        "import glob, os, sys, jax\n"
        "fs = sorted(glob.glob(os.path.join(sys.argv[1], '**', "
        "'*.xplane.pb'), recursive=True), key=os.path.getmtime)\n"
        "assert fs, 'no .xplane.pb under ' + sys.argv[1]\n"
        "pd = jax.profiler.ProfileData.from_file(fs[-1])\n"
        "print(os.path.getsize(fs[-1]), [p.name for p in pd.planes])\n")
    r = subprocess.run([sys.executable, "-c", code, trace_dir],
                       env=dict(env, JAX_PLATFORMS="cpu"), text=True,
                       capture_output=True, timeout=300)
    return (r.stdout.strip() if r.returncode == 0
            else f"unreadable: {r.stderr.strip()[-300:]}")


# ---- the two smokes --------------------------------------------------------

def one_chip(model_dir, env, size, platform, ready_timeout, trim):
    want = dict(platform=platform, count=size["count"])
    serve = run_arm("serve", model_dir, trim, env, size,
                    dict(want, attention_impl="pallas", profile=True),
                    ready_timeout)
    ref = run_arm("serve-ref", model_dir,
                  trim + ["--attention-impl", "xla", "--skip-warmup"]
                  + size["ref_flags"], env, size,
                  dict(want, attention_impl="xla"), ready_timeout)
    compare_prefill(serve, ref)
    fast = run_arm("serve-fast", model_dir,
                   trim + ["--skip-warmup"] + FAST_FLAGS, env, size,
                   dict(want, attention_impl="pallas"), ready_timeout)
    compare_prefill(fast, ref)
    compare_prefill(fast, serve, gate=False)
    check(fast["steps"].get("fused_block", 0) > 0,
          f"serve-fast: no fused_block steps: {fast['steps']}")
    other = {k: v for k, v in fast["steps"].items()
             if k not in FAST_KINDS and v}
    check(not other, f"serve-fast ran other step kinds: {other}")
    if platform == "tpu":
        # the children share one compile cache (the set-up programs at
        # least are the same in every arm)
        for arm in (ref, fast):
            check(arm["cached"] > 0, f"{arm['name']}: read no program "
                                     "back from the compile cache")
    return serve["device"], serve


def four_chips(model_dir, env, size, platform, ready_timeout, trim):
    want = dict(platform=platform, count=size["count"])
    base = trim + ["--skip-warmup"]
    a = run_arm("tp4-pallas", model_dir,
                base + ["--tp", "4", "--attention-impl", "pallas"]
                + size["mesh_flags"], env, size,
                dict(want, attention_impl="pallas"), ready_timeout)
    b = run_arm("tp4-xla", model_dir,
                base + ["--tp", "4", "--attention-impl", "xla"]
                + size["ref_flags"], env, size,
                dict(want, attention_impl="xla"), ready_timeout)
    compare_prefill(a, b)
    c = run_arm("pp4", model_dir,
                base + ["--pp", "4", "--schedule-method",
                        "token_throttling"] + size["mesh_flags"], env, size,
                dict(want, attention_impl=("pallas" if platform == "tpu"
                                           else "xla")), ready_timeout)
    bounds = c["info"]["parallel"]["stage_layers"]
    n_layers = size["layers"]
    check(bounds and len(bounds) == 4 and bounds[0][0] == 0
          and bounds[-1][1] == n_layers
          and all(bounds[i][1] == bounds[i + 1][0] for i in range(3)),
          f"pp4: stage bounds {bounds} do not tile {n_layers} layers")
    stages = {e.get("stage") for e in c["trace_events"]
              if e.get("kind") == "pp_stage"}
    check(stages >= {0, 1, 2, 3}, f"pp4: pp_stage events cover {stages}")
    log(f"[pp4] stage_layers={bounds} pp_stage events cover "
        f"{sorted(stages)}")
    if platform == "tpu":
        for arm in (a, c):
            used = [m["bytes_in_use"] for m in
                    arm["info"]["device"]["memory"]]
            check(len(used) == 4 and min(used) > (1 << 30)
                  and max(used) < size["model_bytes"],
                  f"{arm['name']}: per-device bytes in use {used}: each "
                  "must hold a share (> 1 GiB) and none the whole model "
                  f"({size['model_bytes']} B)")
    return a["device"], a


def model_bytes(m):
    h, layers = m["hidden_size"], m["num_hidden_layers"]
    qkv = h * (m["num_attention_heads"] + 2 * m["num_key_value_heads"]) \
        * m["head_dim"]
    per_layer = qkv + m["num_attention_heads"] * m["head_dim"] * h \
        + 3 * h * m["intermediate_size"]
    embed = m["vocab_size"] * h * (1 if m["tie_word_embeddings"] else 2)
    return 2 * (layers * per_layer + embed)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny model on the CPU backend (Pallas in "
                         "interpret mode); says so, never a chip result")
    args = ap.parse_args()
    t_start = time.monotonic()
    verdict = {"ok": False}
    try:
        check(os.path.isdir(os.path.join(HERE, "gllm_tpu")),
              "gllm_tpu/ is not next to chip_smoke.py: nothing to run")
        os.makedirs(OUT, exist_ok=True)
        env = dict(os.environ, PYTHONPATH=HERE, PYTHONUNBUFFERED="1")
        if not args.cpu_rehearsal:
            # fail in seconds, not after a CPU server has warmed up at
            # real widths: a throw-away child asks jax what it found
            r = subprocess.run(
                [sys.executable, "-c", "import jax; d = jax.devices(); "
                 "print(d[0].platform, len(d))"], env=env, text=True,
                capture_output=True, timeout=300)
            found = r.stdout.split() or ["none", "0"]
            check(r.returncode == 0 and found[0] == "tpu"
                  and int(found[1]) == args.chips,
                  f"need {args.chips} TPU chip(s); jax found "
                  f"{found[1]} x {found[0]} (rc={r.returncode}) "
                  f"{r.stderr.strip()[-200:]}".rstrip())
        if args.cpu_rehearsal:
            log("CPU REHEARSAL: tiny model on the CPU backend. Not a chip "
                "run; no device number below means anything.")
            model = dict(MODEL_1CHIP if args.chips == 1 else MODEL_4CHIP,
                         **TINY_WIDTHS)
            platform = "cpu"
            env["JAX_PLATFORMS"] = "cpu"
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_"
                                f"host_platform_device_count={args.chips}"
                                ).strip()
            trim = ["--max-model-len", "512", "--maxp", "128", "--maxd",
                    "8", "--max-num-seqs", "8", "--page-size", "4",
                    "--dtype", "float32"]
            if args.chips == 1:
                # interpret-mode Pallas must be asked for by name
                trim += ["--attention-impl", "pallas"]
            size = dict(short_prompt=20, decode_tokens=8, long_prompt=150,
                        concurrent=4, count=args.chips,
                        ref_flags=["--maxp", "64", "--num-pages", "512"])
            mesh_flags = []
            ready_timeout = 600
        else:
            model = MODEL_1CHIP if args.chips == 1 else MODEL_4CHIP
            platform = "tpu"
            # Trimmed warm-up grid, never the widths: --maxd bounds the
            # decode buckets the normal warm-up compiles (each costs
            # seconds to tens of seconds cold: ROADMAP, "Set-up").
            trim = ["--maxd", "16", "--max-num-seqs", "16"]
            # The XLA attention path is the CPU platform's own and the
            # oracle; on a TPU it is affordable only small. It pads scores
            # to [seq bucket, max_q, table width] (8.6 GB of f32 for one
            # 2048-token chunk against a 4096-token table) and the TPU
            # compiler copies the whole K and V pool every step (temp = 2x
            # the pool; tests/test_tpu_compile.py shows both). So the
            # reference arms get a small explicit pool and short chunks;
            # chunked prefill is exact, so the comparison stands.
            # Short requests stay inside one 64-token page bucket: every
            # new (rows, pages) bucket is one more program to compile,
            # sampled ones at ~20 s each.
            size = dict(short_prompt=24, decode_tokens=16, long_prompt=2100,
                        concurrent=6, count=args.chips,
                        ref_flags=["--maxp", "256", "--num-pages", "2048"])
            # The pool leaves 10% of the device + 512 MB for a step's
            # temporaries, and prompt logprobs of a 2048-token chunk over
            # this model's 152k vocabulary need 1.76 GiB of them (compile
            # rehearsal): 15.46 of 15.75 GiB. A deployment that serves
            # prompt logprobs gives the pool a smaller share.
            mesh_flags = ["--memory-util", "0.8"]
            ready_timeout = 900
        # sampling is proven on one chip; each sampled program costs the
        # four-chip arms ~25 s of compiling at four times the price
        size.update(sampled=args.chips == 1, mesh_flags=mesh_flags,
                    vocab=model["vocab_size"],
                    layers=model["num_hidden_layers"],
                    model_bytes=model_bytes(model))
        model_dir = os.path.join(OUT, f"model_{args.chips}chip"
                                 + ("_tiny" if args.cpu_rehearsal else ""))
        os.makedirs(model_dir, exist_ok=True)
        with open(os.path.join(model_dir, "config.json"), "w") as f:
            json.dump(dict(model, torch_dtype="bfloat16"), f, indent=1)
        run = one_chip if args.chips == 1 else four_chips
        device, first = run(model_dir, env, size, platform, ready_timeout,
                            trim)
        if first.get("profile_dir"):
            log(f"[profile] planes: "
                f"{plane_names(first['profile_dir'], env)}")
        if args.cpu_rehearsal:
            check(device["platform"] != "tpu", "rehearsal ran on a TPU?")
        verdict = {"ok": True, "device": {
            "platform": device["platform"], "kind": device["kind"],
            "count": device["count"]}}
    except SmokeFailure as e:
        verdict["error"] = str(e)
    except Exception as e:          # a bug in the smoke is a failure too
        verdict["error"] = f"{type(e).__name__}: {e}"
    log(f"[chip_smoke] wall_s={time.monotonic() - t_start:.1f}")
    print(json.dumps(verdict), flush=True)
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
