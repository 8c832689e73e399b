"""run.py end to end on the CPU for the windowed-GQA cell, at the
configuration's rehearsal widths (a window of 24 in 3 of 4 layers, 2 of 16
experts held, 4 shared). The cell, its configuration, its reference, its
kernels and its metrics were added by files alone; the traffic is the
``docqa`` mix and generator the benchmark already had."""

import json
import os
import subprocess
import sys

import _paths


RUN = os.path.join(_paths.BENCH, "run.py")
CELL = "command-a-plus-05-2026.docqa"
M = _paths.manifest()
CELL_FILE = _paths.bench_json("cells", CELL + ".json")
CONFIG = _paths.bench_json("configs", "command-a-plus-05-2026.json")


def test_the_cell_is_the_issues():
    assert CELL_FILE["clients"] == 16 and CELL_FILE["chips"] == 1
    assert CELL_FILE["traffic"] == "docqa"
    entry = [w for w in M["workloads"] if w["name"] == CELL]
    assert len(entry) == 1 and entry[0]["chips"] == 1
    assert entry[0]["why"] == CELL_FILE["why"] and len(entry[0]["why"]) <= 200
    for said in ("16 callers", "window 4096 in 3 of 4",
                 "1 token an expert a step", "1/8 of a chip",
                 "attention 8x"):
        assert said in entry[0]["why"]
    flags = CONFIG["server_flags"]
    val = lambda name: int(flags[flags.index(name) + 1])      # noqa: E731
    assert val("--max-num-seqs") == val("--min-row-bucket") == 16
    assert "--enable-prefix-caching" in flags
    # every caller at its longest, and the page a step reserves ahead
    traffic = _paths.bench_json("traffic", "docqa.json")
    longest = (traffic["document_len"]["max"] + traffic["question_len"]["max"]
               + traffic["output_len"]["max"])
    pages = -(-longest // 16) + 1
    assert pages == 1069 <= val("--min-page-bucket") == 1088
    assert val("--max-model-len") == 1088 * 16
    assert 16 * pages == 17104 <= val("--num-pages") == 17280
    # the probe's chunks: 2048, 2048 and 512 (ISSUE 44's second valve:
    # the float32 reference decides a run's set-up), the last one's
    # queries past the window; the decode probe past the window too
    probe = CONFIG["probe"]
    assert probe["prefill_tokens"] == 4608 > CONFIG["sliding_window"]
    assert probe["decode_prompt_tokens"] == 4352 > CONFIG["sliding_window"]


def bench(*args, timeout=900):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    r = subprocess.run([sys.executable, RUN, "--workload", CELL,
                        "--cpu-rehearsal", *args], cwd=_paths.ROOT, env=env,
                       text=True, capture_output=True, timeout=timeout)
    return r.returncode, r.stdout.strip().splitlines(), r.stderr


def test_rehearsal_prints_the_contracts_last_line_and_is_correct():
    rc, lines, err = bench("--seed", str(2 ** 31 + 144), "--seconds", "4",
                           "--trace", "2")
    assert rc == 0, (lines[-15:], err[-2000:])
    out = json.loads(lines[-1])
    assert set(out) == {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 6
    assert out["device"]["platform"] == "cpu"
    assert any("prefill_rel_rms" in ln and "limit" in ln for ln in lines)
    fill = [ln for ln in lines if ln.startswith("[fill] over")]
    assert len(fill) == 1 and fill[0].endswith("(should be none): []")
    assert any(ln.startswith("[window] step shapes") and ln.endswith(": []")
               for ln in lines)
    metrics = out["metrics"]
    e2e = {m["name"] for m in M["end_to_end"] if "workloads" not in m}
    assert e2e <= set(metrics)
    # 2 of 16 experts held, top 4 of 16, up to 4 rows
    assert 0 < metrics["moe.swa_moe_experts_touched_per_step"]["value"] <= 2
    # ... of which a token's 4 assignments hit 2 / 16: about an eighth
    assert 5 < metrics["moe.swa_moe_held_assignments_pct"]["value"] < 25
    # every admission probed the prefix cache once
    assert metrics["kv.prefix_match_p50_ms"]["value"] > 0
    # documents of 64-128 tokens under a window of 24: the windowed layers
    # read a fifth to a third of what the full layer reads
    assert 10 < metrics["kv.window_rows_read_of_context_pct"]["value"] < 45
    # what reads a device trace has nothing to read on the CPU
    assert not {m["name"] for m in M["per_layer"]
                if m["source"] == "device_trace"} & set(metrics)
    assert metrics["runner.compiles_in_window"]["value"] == 0
    assert metrics["sched.preemptions"]["value"] == 0
    # the server's start-up line: what the chip holds
    with open(os.path.join(_paths.ROOT, "chiprun_out", "perfbench", CELL,
                           "server.log"), errors="replace") as f:
        log = f.readlines()
    said = [ln for ln in log if "[startup] windowed GQA model:" in ln]
    assert len(said) == 1, said
    assert "2 of 16 routed experts a layer held here" in said[0]
    assert "KV pool 512 pages x 4 layers x 256 B a token" in said[0]
    assert "window 24 in 3 of 4 layers; prefix cache on" in said[0]
    assert "grouped products -> xla ragged_dot" in said[0]
    # the requests behind a caller's first were prefix hits: the cache
    # served the documents' whole pages (kv.prefix_hit_tokens_pct lists
    # this cell beside a.x-k1.docqa)
    assert 70 < metrics["kv.prefix_hit_tokens_pct"]["value"] < 95
