"""run.py end to end on the CPU for the short-convolution cell, at the
configuration's rehearsal widths (5 conv + 2 attention layers, heads of 64,
2 of 16 experts held). The cell, its configuration, its reference, its
kernels and its metrics were added by files alone; the traffic is the
``reason`` mix the benchmark already had."""

import json
import os
import subprocess
import sys

import _paths


RUN = os.path.join(_paths.BENCH, "run.py")
CELL = "lfm2-24b-a2b.reason"
M = _paths.manifest()
CELL_FILE = _paths.bench_json("cells", CELL + ".json")


def test_the_cell_is_the_issues():
    assert CELL_FILE["clients"] == 128 and CELL_FILE["chips"] == 1
    assert CELL_FILE["traffic"] == "reason"
    entry = [w for w in M["workloads"] if w["name"] == CELL]
    assert len(entry) == 1 and entry[0]["chips"] == 1
    assert entry[0]["why"] == CELL_FILE["why"] and len(entry[0]["why"]) <= 200
    for said in ("128 callers", "20 KB of KV a token", "8 tokens an expert",
                 "8x their share"):
        assert said in entry[0]["why"]
    flags = _paths.bench_json("configs", "lfm2-24b-a2b.json")["server_flags"]
    rows = int(flags[flags.index("--max-num-seqs") + 1])
    assert rows == CELL_FILE["clients"] == int(
        flags[flags.index("--min-row-bucket") + 1])
    # every caller at its longest, and the page a step reserves ahead
    traffic = _paths.bench_json("traffic", "reason.json")
    longest = traffic["prompt_len"]["max"] + traffic["output_len"]["max"]
    pages = 128 * (-(-longest // 16) + 1)
    assert pages == 16512 <= int(flags[flags.index("--num-pages") + 1])


def bench(*args, timeout=900):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    r = subprocess.run([sys.executable, RUN, "--workload", CELL,
                        "--cpu-rehearsal", *args], cwd=_paths.ROOT, env=env,
                       text=True, capture_output=True, timeout=timeout)
    return r.returncode, r.stdout.strip().splitlines(), r.stderr


def test_rehearsal_prints_the_contracts_last_line_and_is_correct():
    rc, lines, err = bench("--seed", str(2 ** 31 + 151), "--seconds", "4",
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
    # 12 rows x 4 / 16 = 3 tokens an expert: most steps touch both held
    assert 0.5 < metrics["moe.sconv_moe_experts_touched_per_step"][
        "value"] <= 2
    assert 0 < metrics["kv.ssm_slots_peak_pct"]["value"] <= 100
    # another family's readers leave this cell alone, whatever they read
    assert "moe.relu2_experts_touched_per_step" not in metrics
    assert "runner.mamba_chunk_fill_pct" not in metrics
    # what reads a device trace has nothing to read on the CPU
    assert not {m["name"] for m in M["per_layer"]
                if m["source"] == "device_trace"} & set(metrics)
    assert metrics["runner.compiles_in_window"]["value"] == 0
    assert metrics["sched.preemptions"]["value"] == 0
    # the server's start-up lines: what the chip holds and which kernels
    # serve it
    with open(os.path.join(_paths.ROOT, "chiprun_out", "perfbench", CELL,
                           "server.log"), errors="replace") as f:
        log = f.readlines()
    said = [ln for ln in log if "[startup] short-convolution model:" in ln]
    assert len(said) == 1, said
    assert "2 of 16 routed experts a layer held here" in said[0]
    assert "KV pool 512 pages x 8 tokens x 2 attention layers" in said[0]
    pool = [ln for ln in log if "[startup] window pool:" in ln]
    assert len(pool) == 1 and "17 slots x 5 conv layers x 512 bytes" in pool[0]
