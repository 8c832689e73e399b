#!/usr/bin/env python3
"""The benchmark: one cell, one run, one result line.

    python perfbench/run.py --workload <cell> --seed <n> --seconds <s>
                            --trace <0|1|2> [--cpu-rehearsal] [--control]

This parent uses the standard library only and never imports jax: it is the
load generator and must not hold the chip. It starts one
``python -m gllm_tpu.entrypoints.api_server`` child with the
configuration's flags (and, beside it, a CPU-only child that computes the
plain reference), checks the served answers against the reference, warms up
the cell's shapes, offers the cell's traffic for ``--seconds`` seconds,
stops the child and prints one JSON object as its last line.

``--trace 0`` measures (the end-to-end metrics), ``--trace 1`` traces a
run of its own (the per-layer metrics), and ``--trace 2`` does both in one
process: exactly what ``--trace 0`` does until the measured window has
closed and its numbers are frozen, then the same traffic goes on for a
short tail in which the counters are read and the profiler runs
(``Tail``), and the last line holds both kinds of metric.

Everything that belongs to one configuration, traffic mix, cell, per-layer
metric, kernel or reference family is a file of its own, found by name:
``configs/``, ``traffic/``, ``cells/``, ``layer_metrics/``, ``kernels/``,
``reference/``, ``generators/``. ``BENCHMARK.json`` lists the metrics and
cells and holds no traffic parameter.

Without the cell's TPUs the run fails (non-zero exit, no result line);
``--cpu-rehearsal`` runs the same control flow at the configuration's
``rehearsal`` widths on the CPU backend, says ``"platform": "cpu"`` and
means nothing as a measurement. ``--control`` adds the configuration's
``control_flags`` (the program's own lower-precision path): such a run has
to come out ``"correct": false``.
"""

import argparse
import array
import importlib.util
import json
import os
import random
import shutil
import subprocess
import sys
import threading
import time

T_PROCESS_START = time.monotonic()

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

from lib import compare, stats                         # noqa: E402
from lib.loadgen import Load, Req, completion          # noqa: E402
from lib import serving                                # noqa: E402
from lib.serving import (BenchFailure, Server, check,   # noqa: E402
                         prom_samples)

# every control request this process makes, with its instant: a --trace 2
# run has to make, between the load's start and the window's end, exactly
# those a --trace 0 run makes (the ``[requests]`` line says which)
REQUESTS = []


def _noted(method, call):
    def noted(port, path, *args, **kw):
        REQUESTS.append((time.monotonic(), f"{method} {path}"))
        return call(port, path, *args, **kw)
    return noted


get_json = _noted("GET", serving.get_json)
get_text = _noted("GET", serving.get_text)
post_json = _noted("POST", serving.post_json)


def log(msg):
    print(msg, flush=True)


AT = {}         # seconds from the process's start to each stage's end


def stage_done(stage):
    AT[stage] = round(time.monotonic() - T_PROCESS_START, 1)


def load_json(*parts):
    path = os.path.join(HERE, *parts)
    check(os.path.isfile(path), f"no such file: {os.path.relpath(path, CHECKOUT)}")
    with open(path) as f:
        return json.load(f)


def load_module(folder, name):
    """``perfbench/<folder>/<name>.py`` as a module."""
    path = os.path.join(HERE, folder, name + ".py")
    check(os.path.isfile(path), f"no such file: perfbench/{folder}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{folder}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_peaks(device_kind):
    """The chip's published peaks. An unlisted device is an error."""
    table = load_json("peaks.json")["devices"]
    if device_kind not in table:
        raise BenchFailure(f"device kind {device_kind!r} is not in "
                           "perfbench/peaks.json: add it with its source")
    return table[device_kind]


HF_SKIP = ("name", "source", "reduced", "reduced_why", "assumed", "chips",
           "deployment", "reference", "stage_layers", "server_flags",
           "control_flags", "probe", "derived", "rehearsal", "correct",
           "trace_patterns")


def model_of(config):
    """The published ``config.json`` keys of a configuration file."""
    return {k: v for k, v in config.items() if k not in HF_SKIP}


# ---- the reference child ---------------------------------------------------

class Reference:
    """The CPU-only child that computes the plain reference, started with
    the first question. No answer is kept from run to run: a seed that
    comes again pays again, so that two runs of one seed do the same work
    at the same instants."""

    def __init__(self, config, model, seed, dtype, log_path):
        self.head = {"family": config["reference"], "model": model,
                     "seed": seed, "dtype": dtype,
                     "stage_layers": config.get("stage_layers")}
        self.log_path, self.log_file, self.proc = log_path, None, None
        self.answers = {}

    def _start(self):
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONUNBUFFERED="1")
        env.pop("XLA_FLAGS", None)
        self.log_file = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "lib", "refchild.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=self.log_file, env=env, text=True, cwd=CHECKOUT)
        self.send(self.head)
        self.reader = threading.Thread(target=self._read, daemon=True)
        self.reader.start()

    def send(self, obj):
        self.proc.stdin.write(json.dumps(obj) + "\n")
        self.proc.stdin.flush()

    def _read(self):
        for line in self.proc.stdout:
            if line.startswith("RESULT "):
                ans = json.loads(line[7:])
                self.answers[ans["id"]] = ans

    def ask(self, job_id, tokens, want, control=None):
        if self.proc is None:
            self._start()
        self.send({"id": job_id, "tokens": tokens, "want": want,
                   "control": control})

    def wait(self, job_id, timeout):
        t0 = time.monotonic()
        while job_id not in self.answers:
            if self.proc.poll() is not None:
                self.reader.join(timeout=5)
                if job_id in self.answers:
                    break
                raise BenchFailure("the reference child exited with code "
                                   f"{self.proc.returncode}")
            if time.monotonic() - t0 > timeout:
                raise BenchFailure(f"no reference answer for {job_id!r} "
                                   f"after {timeout:.0f}s")
            time.sleep(0.2)
        return self.answers[job_id]

    def close(self):
        if self.proc is None:
            return
        if self.proc.poll() is None:
            try:
                self.proc.stdin.close()
                self.proc.wait(timeout=30)
            except (OSError, subprocess.TimeoutExpired):
                self.proc.kill()
                self.proc.wait()
        self.log_file.close()


# ---- probes: what the comparison reads from the served side ----------------

def probe_prefill(port, tokens):
    """Per-position logprobs the server gives the probe's own tokens
    (positions 1..n-1); the probe is longer than --maxp, so it is prefilled
    in chunks."""
    out = completion(port, tokens, 1, echo=True, prompt_logprobs=1,
                     logprobs=1)
    lp = out["choices"][0]["logprobs"]["token_logprobs"]
    check(len(lp) == len(tokens) + 1, f"echo returned {len(lp)} entries "
                                      f"for {len(tokens)} tokens + 1")
    return lp[1:len(tokens)]


def probe_decode(port, prompt, n_out, top):
    """Decode ``n_out`` greedy tokens through the cache; per step the
    server's ``top`` most likely tokens with their logprobs."""
    out = completion(port, prompt, n_out, logprobs=top)
    lp = out["choices"][0]["logprobs"]
    tokens = [int(t) for t in lp["tokens"]]
    check(len(tokens) == n_out, f"decode probe returned {len(tokens)} "
                                f"tokens, want {n_out}")
    tops = [{int(k): v for k, v in step.items()}
            for step in lp["top_logprobs"]]
    return tokens, tops


# ---- warm-up: the cell's own shapes ----------------------------------------

def warm_up(port, script, vocab, rng):
    """Drive the server through the step shapes the cell's traffic uses.

    A step program is compiled per (tokens, rows, pages) bucket, ~25 s each
    on a cold v5e. ``script`` is the cell's list of steps, run in order:

    - {"hold": n, "ctx": c, "tag": t}: start n more streams that keep
      decoding (the rows bucket), one by one, each when the one before has
      its first token, so that every joining step carries one prompt and
      the steps are the same in every run; ``ctx`` tokens of context each
      (default 16; the longest context sets the pages bucket);
    - {"prompts": [..]}: one prompt of each length, each waiting for the one
      before, so each makes one mixed step of its own tokens bucket;
    - {"drop": t}: cut the streams tagged t.

    Every stream is cut at the end.
    """
    load = Load(port)
    load.clock.zero = time.monotonic()
    held, n_prompts, t0 = [], 0, time.monotonic()
    try:
        for step in script:
            if "hold" in step:
                new = [Req(len(held) + i,
                           rng.choices(range(2, vocab),
                                       k=step.get("ctx", 16)),
                           step.get("tokens", 1500))
                       for i in range(step["hold"])]
                for r in new:
                    r.client = step.get("tag")
                    load.launch(r)
                    wait_tokens([r], 1, 900)
                held += new
                wait_tokens(held, 2, 900)
            elif "prompts" in step:
                for p in step["prompts"]:
                    completion(port, rng.choices(range(2, vocab), k=p), 1)
                    n_prompts += 1
            elif "drop" in step:
                gone = [r for r in held if r.client == step["drop"]]
                load.cut(gone)
                held = [r for r in held if r.client != step["drop"]]
            log(f"[warmup] {json.dumps(step)} done at "
                f"{time.monotonic() - t0:.1f}s")
    finally:
        load.stop()
    bad = [r.status for r in load.records if r.status.startswith("failed")]
    check(not bad, f"a warm-up stream failed: {bad[:1]}")
    return n_prompts


def wait_idle(port, timeout=120):
    """Until the server holds no sequence: streams that were cut are
    aborted at the server's next step, not at once."""
    t0 = time.monotonic()
    while True:
        info = get_json(port, "/server_info")
        if not info["waiting"] and not info["running"]:
            return
        check(time.monotonic() - t0 < timeout,
              "the server did not drain after the warm-up")
        time.sleep(0.1)


def wait_tokens(reqs, n, timeout):
    t0 = time.monotonic()
    while any(len(r.times) < n and r.status == "planned" for r in reqs):
        check(time.monotonic() - t0 < timeout,
              "warm-up streams did not start decoding")
        time.sleep(0.02)
    bad = [r.status for r in reqs if r.status.startswith("failed")]
    check(not bad, f"a warm-up stream failed: {bad[:1]}")


# ---- the run ---------------------------------------------------------------

class Session:
    """One serving child with the reference beside it: bring-up, the
    comparison, the warm-up, and any number of loads (run.py offers one,
    sweep.py one per rate)."""

    def __init__(self, workload, seed, rehearsal=False, control=False,
                 verify=True):
        self.verify = verify
        check(os.path.isdir(os.path.join(CHECKOUT, "gllm_tpu")),
              "gllm_tpu/ is not in this checkout: nothing to serve")
        self.workload, self.seed, self.rehearsal = workload, seed, rehearsal
        with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
            self.manifest = json.load(f)
        cell = load_json("cells", workload + ".json")
        config = load_json("configs", cell["config"] + ".json")
        traffic = load_json("traffic", cell["traffic"] + ".json")
        model = model_of(config)
        flags = list(config["server_flags"])
        probe = dict(config["probe"])
        if rehearsal:
            reh = config.get("rehearsal", {})
            model.update(reh.get("model", {}))
            flags = list(reh.get("server_flags", flags))
            probe.update(reh.get("probe", {}))
            cell = dict(cell, **cell.get("rehearsal", {}))
            traffic = dict(traffic, **traffic.get("rehearsal", {}))
            config = dict(config,
                          correct=reh.get("correct", config["correct"]),
                          stage_layers=reh.get("stage_layers",
                                               config.get("stage_layers")))
            log("CPU REHEARSAL: tiny widths on the CPU backend. Not a chip "
                "run; no time below means anything.")
        if control:
            flags += config["control_flags"]
        self.cell, self.config, self.traffic = cell, config, traffic
        self.model, self.flags, self.probe = model, flags, probe
        self.generator = load_module("generators", traffic["generator"])
        self.vocab = model["vocab_size"]
        self.rng = random.Random(seed ^ 0x5EED)

        self.out_dir = os.path.join(CHECKOUT, "chiprun_out", "perfbench",
                                    workload)
        model_dir = os.path.join(self.out_dir, "model")
        self.trace_dir = os.path.join(self.out_dir, "trace")
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.makedirs(model_dir, exist_ok=True)
        with open(os.path.join(model_dir, "config.json"), "w") as f:
            json.dump(model, f, indent=1)
        env = dict(os.environ, PYTHONPATH=CHECKOUT, PYTHONUNBUFFERED="1",
                   GLLM_PROFILE_DIR=self.trace_dir,
                   GLLM_OBS_TRACE_CAP="262144")
        env.pop("BENCH_RUN", None)
        if rehearsal:
            env["JAX_PLATFORMS"] = "cpu"
            env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                                f"{cell['chips']}")
        else:
            # jax then fails at start-up where there is no TPU, in seconds,
            # instead of serving billions of parameters from the CPU
            env["JAX_PLATFORMS"] = "tpu"
        self.env = env
        self.server = Server(CHECKOUT, model_dir, flags, env,
                             os.path.join(self.out_dir, "server.log"),
                             ready_timeout=1100, seed=seed)
        self.reference = None
        self.load = None
        self.peaks = None

    def start(self):
        """Start both children; wait for the server; check its device."""
        self.server.start()
        if self.verify:
            self.reference = Reference(
                self.config, self.model, self.seed,
                self.flags[self.flags.index("--dtype") + 1],
                os.path.join(self.out_dir, "reference.log"))
            rng, probe, vocab = self.rng, self.probe, self.vocab
            self.long_probe = rng.choices(range(2, vocab),
                                          k=probe["prefill_tokens"])
            self.dec_prompt = rng.choices(range(2, vocab),
                                          k=probe["decode_prompt_tokens"])
            self.reference.ask("prefill", self.long_probe,
                               [[t] for t in self.long_probe[1:]] + [[]])
        self.server.wait_ready()
        self.port = self.server.port
        info = get_json(self.port, "/server_info")
        self.dev = dev = info["device"]
        log(f"[run] server ready after "
            f"{time.monotonic() - T_PROCESS_START:.1f}s: {dev['count']} x "
            f"{dev['kind']} ({dev['platform']}), {info['num_pages']} pages "
            f"of {info['page_size']}, attention {info['attention_impl']}")
        want = "cpu" if self.rehearsal else "tpu"
        check(dev["platform"] == want
              and dev["count"] == self.cell["chips"],
              f"this cell needs {self.cell['chips']} x {want}; the server "
              f"found {dev['count']} x {dev['platform']}")
        stages = self.config.get("stage_layers")
        if stages:
            check(info["parallel"]["stage_layers"] == stages,
                  f"stage layers {info['parallel']['stage_layers']} are "
                  f"not the configuration's {stages}")
        if not self.rehearsal:
            self.peaks = load_peaks(dev["kind"])
        self.info = info

    def probe_served(self):
        """The served side of the comparison; the reference is asked for
        the decode probe as soon as the served tokens are known."""
        t0 = time.monotonic()
        self.served_prefill = probe_prefill(self.port, self.long_probe)
        tokens, self.dec_tops = probe_decode(
            self.port, self.dec_prompt, self.probe["decode_tokens"],
            self.probe["decode_top"])
        log(f"[probe] served both probes in {time.monotonic() - t0:.1f}s")
        n_p = len(self.dec_prompt)
        self.dec_full = self.dec_prompt + tokens
        self.dec_want = [[] for _ in self.dec_full]
        for j, top in enumerate(self.dec_tops):
            self.dec_want[n_p - 1 + j] = sorted(top)
        self.reference.ask("decode", self.dec_full, self.dec_want)

    def warm(self):
        script = self.cell.get("warmup")
        if script:
            t0 = time.monotonic()
            mark = get_json(self.port, "/steptrace?kind=none")["next_since"]
            n = warm_up(self.port, script, self.vocab, self.rng)
            wait_idle(self.port)
            log(f"[warmup] {n} prompts in {time.monotonic() - t0:.1f}s; "
                f"step shapes (tokens, rows, pages) built: "
                f"{self.shapes_since(mark)}")

    def verdict(self):
        """Wait for the reference and compare. Before the load begins:
        the reference must not share the CPU with the load generator in
        the window, nor with the server in the ramp, which lasts a fixed
        time: the steps it holds decide where in their answers the callers
        are when the window opens, and with that every number read."""
        ref_prefill = self.reference.wait("prefill", 900)
        ref_decode = self.reference.wait("decode", 900)
        log(f"[reference] prefill probe {ref_prefill['seconds']:.1f}s, "
            f"decode probe {ref_decode['seconds']:.1f}s")
        self.reference.close()
        verdict = compare.verdict(
            self.served_prefill,
            [v[0] for v in ref_prefill["logprobs"][:-1]], self.dec_tops,
            ref_decode["logprobs"][len(self.dec_prompt) - 1:],
            self.config["correct"])
        for line in verdict["lines"]:
            log("[correct] " + line)
        self.verdict_of_run = verdict
        stage_done("comparison")

    def offer(self, seconds, trace=0, cell=None, seed=None, before=None):
        """Offer the cell's traffic for ``seconds`` after its ramp. Returns
        (records, window, the load's first instant on time.monotonic()).
        ``before`` is called before the load begins (the comparison). A
        generator that ``OPENS_WHEN_READY`` fills the server by events and
        says when: the window opens ``settle_s`` after ``ramp_s`` have
        passed, or after the fill's end where that is later. Any other
        plans its requests against a window that opens ``ramp_s`` after
        the start."""
        cell, traffic = cell or self.cell, self.traffic
        by_event = getattr(self.generator, "OPENS_WHEN_READY", False)
        if before is not None:
            before()
        seed = self.seed if seed is None else seed
        reqs = self.generator.plan(traffic, cell, seed, seconds,
                                   self.vocab)
        mark = get_json(self.port, "/steptrace?kind=none")["next_since"]
        load = self.load = Load(self.port)
        send_until = seconds        # the generator's last instant to send
        if trace == 2:
            # the same traffic goes on through the tail, to the instant
            # the load is stopped: a closed loop's callers just keep
            # sending (they have requests for minutes); an open loop's
            # tail is a second plan from a derived seed, so that the
            # window's own plan does not change with it
            window = Tail(self.port, seconds, traffic, self.out_dir, mark)
            send_until = seconds + window.plan_s
            if not by_event:
                more = self.generator.plan(
                    dict(traffic, ramp_s=0.0), cell, seed ^ 0x7A11,
                    window.plan_s, self.vocab)
                for r in more:
                    r.idx, r.due = len(reqs), r.due + seconds
                    reqs.append(r)
        else:
            window = Window(self.port, seconds, trace, traffic,
                            self.out_dir)
        t0 = self.t_load = time.monotonic()
        if by_event:
            load.clock.zero = t0                        # provisional
            ready = self.generator.start(load, reqs, send_until, traffic)
            window.watch(load)
            while not ready.wait(0.2):
                check(time.monotonic() - t0 < 900, "the fill did not end")
            bad = [r.status for r in load.records
                   if r.status.startswith("failed")]
            check(not bad, f"a request of the fill failed: {bad[:1]}")
            fill_shapes = self.shapes_since(mark)
            log(f"[fill] over after {time.monotonic() - t0:.1f}s; step "
                f"shapes first used in it (should be none): {fill_shapes}")
            load.open_window(max(t0 + traffic["ramp_s"], time.monotonic())
                             + traffic.get("settle_s", 2.0))
        else:
            load.clock.zero = t0 + traffic["ramp_s"] + 0.05
            load.opened.set()
            self.generator.start(load, reqs, send_until, traffic)
            window.watch(load)
        load.clock.sleep_until(0.0)
        stage_done("window open")
        load.clock.sleep_until(seconds)
        drain_until = seconds + traffic.get("drain_s", 0)
        while load.clock.now() < drain_until and any(
                r.due is not None and 0 <= r.due < seconds
                and not len(r.times) and r.status == "planned"
                for r in list(load.records)):
            time.sleep(0.05)
        stage_done("window shut")
        self.t_shut = time.monotonic()
        frozen = None
        if trace == 2:
            # the instant --trace 0 stops the load: what had happened by
            # now is the measured window's, whatever the tail adds
            frozen = freeze(load)
            window.run(load)
            stage_done("tail over")
        load.stop()
        window.join()
        self.new_shapes = self.shapes_since(mark)
        log(f"[window] step shapes (tokens, rows, pages) first used after "
            f"the load began, ramp included: {self.new_shapes}")
        window.records = [r for r in load.records if r.due is not None]
        records = window.records if frozen is None else frozen
        return records, window, load.clock.zero

    def shapes_since(self, mark):
        """(tokens, rows, pages) of the step programs first used since."""
        return [(e.get("tokens_pad"), e.get("seqs_pad"), e.get("pages_pad"))
                for e in get_json(self.port, f"/steptrace?since={mark}"
                                  "&kind=compile", timeout=300)["events"]]

    def compiled(self):
        return prom_samples(get_text(self.port, "/metrics"),
                            "gllm_xla_programs_total").get(
                                '{source="compiled"}', 0)

    def close(self):
        """Stop everything this session started. Returns the server's exit
        code."""
        if self.load is not None and not self.load.stopping.is_set():
            try:
                self.load.stop()
            except RuntimeError:
                pass
        if self.reference is not None:
            self.reference.close()
        return self.server.stop()


def run(args):
    session = Session(args.workload, args.seed, args.cpu_rehearsal,
                      args.control)
    seconds = args.seconds
    try:
        session.start()
        stage_done("server ready")
        session.probe_served()
        stage_done("probes")
        session.warm()
        stage_done("warm-up")
        compiled0 = session.compiled()
        records, window, zero = session.offer(seconds, args.trace,
                                              before=session.verdict)
        verdict = session.verdict_of_run
        setup_s = zero - T_PROCESS_START
        new_compiles = session.compiled() - compiled0
        info_after = get_json(session.port, "/server_info")
    except BenchFailure:
        session.server.tail()
        raise
    except (OSError, RuntimeError, KeyError, ValueError) as e:
        session.server.tail()
        raise BenchFailure(f"{type(e).__name__}: {e}")
    finally:
        rc = session.close()
    check(rc == 0, f"server exit code {rc} after SIGTERM (want 0)")
    stage_done("server gone")

    manifest, dev = session.manifest, session.dev
    e2e = stats.end_to_end(records, seconds)
    e2e["setup_s"] = setup_s
    log(f"[window] {json.dumps(stats.describe(records, seconds))}")
    log(f"[window] programs compiled between the load's start and its "
        f"end: {new_compiles:.0f}")

    def in_cell(m):
        return "workloads" not in m or args.workload in m["workloads"]

    between = [what for t, what in REQUESTS
               if session.t_load <= t < session.t_shut]
    log(f"[requests] control requests between the load's start and the "
        f"window's end: {json.dumps(between)}")
    metrics = {}
    if args.trace != 1:
        for m in manifest["end_to_end"]:
            if in_cell(m) and e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    breakdown = None
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"]}
    mem = [m for m in info_after["device"]["memory"] if m]
    device["memory_peak_bytes"] = max(
        (m.get("peak_bytes_in_use") or m.get("bytes_in_use") or 0
         for m in mem), default=0)
    if args.trace:
        reduced = window.reduce(session.config, session.env,
                                args.cpu_rehearsal, args.keep_trace)
        run_data = dict(
            cell=session.cell, config=session.config, model=session.model,
            traffic=session.traffic, seconds=seconds,
            records=window.records,
            prom0=window.prom0, prom1=window.prom1, steps=window.steps,
            kv_util=window.kv_util, trace=reduced, slice=window.slice,
            peaks=session.peaks, info=info_after, load_module=load_module)
        if args.trace == 2:
            # beside the keys the readers have always had: the trace
            # itself, its idle time by host span (host_gaps.py), and the
            # steptrace events of the measured window
            run_data.update(trace_dir=window.trace_dir,
                            host_gaps=window.gaps,
                            window_steps=window.window_steps)
        for m in manifest["per_layer"]:
            if not in_cell(m):
                continue
            value = load_module("layer_metrics", m["name"]).read(run_data)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if reduced:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            breakdown = reduced["breakdown"]
        if args.trace == 2:
            breakdown = window.finish(breakdown, args.keep_trace)
    with open(os.path.join(session.out_dir, f"records.seed{args.seed}."
                           f"trace{args.trace}.json"), "w") as f:
        json.dump({"e2e": e2e, "metrics": metrics, "verdict": verdict,
                   "requests": stats.dump(records)}, f)
    for name, m in metrics.items():
        log(f"[metric] {name} = {m['value']} {m['unit']}")
    stage_done("result")
    log(f"[time] seconds from the process's start: {json.dumps(AT)}")
    attempted, failed = stats.attempts(records, seconds)
    result = {"correct": bool(verdict["correct"]), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    return result


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1, 2), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--keep-trace", action="store_true",
                    help="keep the .xplane.pb and write its structure "
                         "to trace_dump.json (for a look by hand)")
    args = ap.parse_args()
    try:
        result = run(args)
    except BenchFailure as e:
        log(f"[run] FAILED: {e}")
        return 1
    print(json.dumps(result), flush=True)
    return 0


class Window:
    """What is read at the edges of the window and inside it: /metrics and
    /steptrace at both ends, the KV gauge once a second, and in a traced
    run a profiler capture in the middle."""

    def __init__(self, port, seconds, trace, traffic, out_dir):
        self.port, self.seconds, self.trace = port, seconds, trace
        self.out_dir = out_dir
        self.trace_s = min(traffic.get("trace_s", 3.0), seconds / 2)
        self.prom0 = self.prom1 = None
        self.steps, self.kv_util, self.slice = [], [], None
        self.thread = None
        self.error = None

    def watch(self, load):
        if self.trace == 1:
            self.thread = threading.Thread(target=self._watch, args=(load,),
                                           daemon=True)
            self.thread.start()

    def _watch(self, load):
        try:
            port, clock = self.port, load.clock
            while not load.opened.wait(0.2):
                if load.stopping.is_set():
                    return
            clock.sleep_until(0.0)
            self.prom0 = get_text(port, "/metrics")
            mark = get_json(port, "/steptrace?kind=none")["next_since"]
            t_start = (self.seconds - self.trace_s) / 2
            t_stop = t_start + self.trace_s
            started = stopped = False
            tick = 0.5
            while clock.now() < self.seconds - 0.2:
                now = clock.now()
                if not started and now >= t_start:
                    post_json(port, "/start_profile")
                    s0 = clock.now()
                    started = True
                elif started and not stopped and now >= t_stop:
                    s1 = clock.now()
                    post_json(port, "/stop_profile", timeout=300)
                    self.slice = (s0, s1)
                    stopped = True
                elif now >= tick:
                    text = get_text(port, "/metrics")
                    util = prom_samples(text, "gllm_sched_kv_util")
                    if util:
                        self.kv_util.append(max(util.values()))
                    tick = now + 1.0
                time.sleep(0.05)
            clock.sleep_until(self.seconds)
            self.prom1 = get_text(port, "/metrics")
            events = get_json(port, f"/steptrace?since={mark}",
                              timeout=300)["events"]
            self.steps = events
        except (BenchFailure, OSError, ValueError, KeyError) as e:
            self.error = e

    def join(self):
        if self.thread is not None:
            self.thread.join(timeout=600)
            check(not self.thread.is_alive(), "the window's reader hangs")
            if self.error is not None:
                raise BenchFailure(f"reading the window: {self.error}")

    keep_trace_dir = False      # Tail: host_gaps.py reads it as well

    def reduce(self, config, env, rehearsal, keep):
        """The trace, reduced by a CPU-only child after the server has
        gone. None where there is no device plane (a CPU rehearsal)."""
        out_path = os.path.join(self.out_dir, "trace_reduced.json")
        cmd = [sys.executable, os.path.join(HERE, "trace_reduce.py"),
               "--trace-dir", os.path.join(self.out_dir, "trace"),
               "--patterns", json.dumps(config.get("trace_patterns", {})),
               "--out", out_path]
        if keep:
            cmd += ["--dump", os.path.join(self.out_dir, "trace_dump.json")]
        r = subprocess.run(cmd, env=dict(env, JAX_PLATFORMS="cpu"),
                           text=True, capture_output=True, timeout=600)
        if r.returncode != 0:
            if rehearsal:
                log("[trace] no device plane in a CPU rehearsal: "
                    + r.stderr.strip()[-200:])
                return None
            raise BenchFailure("trace reduction failed: "
                               + r.stderr.strip()[-600:])
        if not keep and not self.keep_trace_dir:
            shutil.rmtree(os.path.join(self.out_dir, "trace"),
                          ignore_errors=True)
        with open(out_path) as f:
            return json.load(f)


def freeze(load):
    """A copy of the load's records as they stand at this instant, for
    the end-to-end numbers of a --trace 2 run: what --trace 0 would hold
    after stopping the load here (an open stream counts as cut now)."""
    with load.lock:
        now, out = load.clock.now(), []
        for r in list(load.records):
            if r.due is None:
                continue
            c = Req(r.idx, r.prompt, r.max_tokens, due=r.due,
                    client=r.client)
            c.sent, c.times = r.sent, array.array("d", r.times)
            c.status = "cut" if r.status == "planned" else r.status
            c.ended = now if r.ended is None else r.ended
            out.append(c)
    return out


def host_ms_per_decode_step(events):
    """Mean host time (the phases other than ``collect``) of the decode
    steps among steptrace events, and how many there were."""
    host = [sum(ms for name, ms in e["ph"].items() if name != "collect")
            for e in events
            if e.get("kind") == "decode" and isinstance(e.get("ph"), dict)]
    return (sum(host) / len(host) if host else None), len(host)


class Tail(Window):
    """What a --trace 2 run does AFTER its measured window has closed and
    its numbers are frozen, while the same traffic goes on: the profiler
    is started and stopped once and that trace thrown away (the first
    start of a process costs what no later one does: it falls into no
    number), then /metrics and the steptrace at both ends of the tail,
    the KV gauge once a second, and a capture of ``trace_s`` seconds in
    its middle, a margin from each end. The readers get these under the
    keys the window's sources have in a --trace 1 run."""

    keep_trace_dir = True
    margin_s = 1.0

    def __init__(self, port, seconds, traffic, out_dir, mark):
        super().__init__(port, seconds, 0, traffic, out_dir)
        self.mark = mark            # the steptrace before the load began
        self.trace_dir = os.path.join(out_dir, "trace")
        # how long the traffic is planned to go on: the throwaway start
        # and stop, the two margins, the capture, and the seconds the
        # profiler takes to hand a capture over at its stop (measured on
        # the v5e: PERF.md), with as much again to spare
        self.plan_s = 2 * self.margin_s + self.trace_s + 120.0
        self.window_steps, self.gaps = [], None

    def run(self, load):
        port, zero = self.port, load.clock.zero
        post_json(port, "/start_profile")
        post_json(port, "/stop_profile", timeout=300)
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        self.prom0 = get_text(port, "/metrics")
        t_lo = time.monotonic()
        time.sleep(self.margin_s)
        started = post_json(port, "/start_profile")
        t_stop = time.monotonic() + self.trace_s
        while time.monotonic() < t_stop:
            util = prom_samples(get_text(port, "/metrics"),
                                "gllm_sched_kv_util")
            if util:
                self.kv_util.append(max(util.values()))
            time.sleep(max(0.0, min(1.0, t_stop - time.monotonic())))
        stopped = post_json(port, "/stop_profile", timeout=300)
        self.stop_s = time.monotonic() - t_stop
        # the server's own clock at the start and the stop: one machine,
        # one CLOCK_MONOTONIC, so the slice lies on the load's clock
        # without the requests' round trips
        self.slice = (started["t_monotonic"] - zero,
                      stopped["t_monotonic"] - zero)
        time.sleep(self.margin_s)
        self.prom1 = get_text(port, "/metrics")
        t_hi = time.monotonic()
        ring = get_json(port, f"/steptrace?since={self.mark}", timeout=300)
        at = [(ring["t0"] + e["t"], e) for e in ring["events"]]
        self.steps = [e for t, e in at if t_lo <= t <= t_hi]
        self.window_steps = [e for t, e in at
                             if zero <= t < zero + self.seconds]
        traced = [e for t, e in at if self.slice[0] <= t - zero
                  <= self.slice[1]]
        on, n_on = host_ms_per_decode_step(traced)
        off, n_off = host_ms_per_decode_step(self.window_steps)
        if on is not None and off:
            log(f"[trace] host phases per decode step with the capture "
                f"running: {on:.3f} ms over {n_on} steps of the traced "
                f"slice; without, in the measured window: {off:.3f} ms "
                f"over {n_off} steps; inflation "
                f"{100.0 * (on / off - 1.0):+.2f} %")
        log(f"[trace] /stop_profile answered after {self.stop_s:.2f} s; "
            f"slice {self.slice[0]:.2f}-{self.slice[1]:.2f} s on the "
            f"load's clock")

    def reduce(self, config, env, rehearsal, keep):
        """Both reductions of the one trace, side by side."""
        out_path = os.path.join(self.out_dir, "host_gaps.json")
        gaps = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "host_gaps.py"),
             "--trace-dir", self.trace_dir, "--patterns",
             json.dumps(config.get("trace_patterns", {})),
             "--out", out_path],
            env=dict(env, JAX_PLATFORMS="cpu"), text=True,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE)
        try:
            reduced = super().reduce(config, env, rehearsal, keep)
        finally:
            _, err = gaps.communicate(timeout=600)
        if gaps.returncode == 0:
            with open(out_path) as f:
                self.gaps = json.load(f)
        elif rehearsal or reduced is None:
            log("[trace] no idle time by host span: " + err.strip()[-200:])
        else:
            raise BenchFailure("host_gaps.py failed: "
                               + err.strip()[-600:])
        return reduced

    def finish(self, breakdown, keep):
        """The idle gaps named by the host span that covers them, the
        sum the span metrics have to meet, and the trace deleted."""
        g = self.gaps
        if g is not None:
            if breakdown is not None:
                breakdown = dict(breakdown, idle_gaps=g["idle_gaps"],
                                 idle_gaps_named_by=g["idle_gaps_named_by"])
            total = sum(g["idle_pct_by_phase"].values())
            log(f"[trace] device idle {g['idle_pct']:.3f} % of the slice "
                f"= " + " + ".join(f"{k} {v:.3f}" for k, v in
                                   g["idle_pct_by_phase"].items())
                + f" = {total:.3f} (identity error "
                f"{g['identity_error_pct']:.4f} %); clock check: "
                f"{json.dumps(g['clock'])}")
        if not keep:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
        return breakdown


if __name__ == "__main__":
    sys.exit(main())
