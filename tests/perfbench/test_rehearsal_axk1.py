"""run.py end to end on the CPU for the document cell, at the
configuration's rehearsal widths (dense latent attention with YaRN, 2 of 32
experts held, the prefix cache on), and the traffic generator that the cell
brings (``generators/docqa.py``). The cell, its configuration, its traffic
mix and generator, its reference, its kernels and its metrics were added by
files alone. (The lower-precision control at these widths is
tests/perfbench/test_reference_axk1.py's.)"""

import importlib.util
import json
import os
import subprocess
import sys
from collections import Counter

import _paths

RUN = os.path.join(_paths.BENCH, "run.py")
CELL = "a.x-k1.docqa"
M = _paths.manifest()
TRAFFIC = _paths.bench_json("traffic", "docqa.json")
CELL_FILE = _paths.bench_json("cells", CELL + ".json")


def _generator():
    path = os.path.join(_paths.BENCH, "generators", "docqa.py")
    spec = importlib.util.spec_from_file_location("t_gen_docqa", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_traffic_is_the_issues_and_a_callers_requests_share_its_document():
    assert TRAFFIC["document_len"] == {"dist": "uniform", "min": 8192,
                                       "max": 16384}
    assert TRAFFIC["question_len"] == {"dist": "uniform", "min": 192,
                                       "max": 448}
    assert TRAFFIC["output_len"] == {"dist": "uniform", "min": 128,
                                     "max": 256}
    assert CELL_FILE["clients"] == 32 and CELL_FILE["chips"] == 1
    gen = _generator()
    traffic = dict(TRAFFIC, requests_per_client=3)
    plans = [gen.plan(traffic, CELL_FILE, seed, 45, 20480)
             for seed in (2 ** 31 + 5, 17)]
    for reqs in plans:
        assert len(reqs) == 32 * 3
        by_client = {}
        for r in reqs:
            by_client.setdefault(r.client, []).append(r)
        for mine in by_client.values():
            docs = {id(r.prompt.doc) for r in mine}
            assert len(docs) == 1                   # ONE document, shared
            assert len({r.prompt.shared for r in mine}) == 1
            whole = [r.prompt.whole() for r in mine]
            n = mine[0].prompt.shared
            assert all(w[:n] == whole[0][:n] and w.shared == n
                       for w in whole)
            # fresh questions behind it
            assert whole[0][n:] != whole[1][n:]
            assert all(len(w) == len(r.prompt) for w, r in zip(whole, mine))
            assert all(192 <= len(w) - n <= 448 for w in whole)
            assert 8192 <= n <= 16384
            first = TRAFFIC["first"]["output_len"]
            assert first["min"] <= mine[0].max_tokens <= first["max"]
            assert all(128 <= r.max_tokens <= 256 for r in mine[1:])
    # every seed gets the same multisets of lengths, in another order
    sizes = [(Counter(r.prompt.shared for r in reqs if r.idx % 3 == 0),
              Counter(len(r.prompt) - r.prompt.shared for r in reqs),
              Counter(r.max_tokens for r in reqs)) for reqs in plans]
    assert sizes[0] == sizes[1]
    assert [r.prompt.shared for r in plans[0]] != [
        r.prompt.shared for r in plans[1]]
    # the documents are 393 k tokens whatever the seed; with the longest
    # question and answer and a page ahead every caller fits the pool
    docs = [r.prompt.shared for r in plans[0] if r.idx % 3 == 0]
    assert sum(docs) == 32 * 12288
    pages = sum(-(-(d + 448 + 256) // 16) + 1 for d in docs)
    flags = _paths.bench_json("configs", "a.x-k1.json")["server_flags"]
    assert pages < int(flags[flags.index("--num-pages") + 1])
    # what is kept of a request that has ended
    done = gen.Unsent(len(plans[0][0].prompt), plans[0][0].prompt.shared)
    assert len(done) == len(plans[0][0].prompt) and done.doc is None
    # a Prompt is a list: the load generator's json.dumps sends it as one
    assert json.loads(json.dumps(gen.Prompt([3, 4]))) == [3, 4]


def bench(*args, timeout=900):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    r = subprocess.run([sys.executable, RUN, "--workload", CELL,
                        "--cpu-rehearsal", *args], cwd=_paths.ROOT, env=env,
                       text=True, capture_output=True, timeout=timeout)
    return r.returncode, r.stdout.strip().splitlines(), r.stderr


def test_traced_rehearsal_is_correct_hits_the_cache_and_meets_no_new_shape():
    rc, lines, err = bench("--seed", str(2 ** 31 + 142), "--seconds", "4",
                           "--trace", "2")
    assert rc == 0, (lines[-15:], err[-2000:])
    out = json.loads(lines[-1])
    assert set(out) == {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 10
    assert any("prefill_rel_rms" in ln and "limit" in ln for ln in lines)
    fill = [ln for ln in lines if ln.startswith("[fill] over")]
    assert len(fill) == 1 and fill[0].endswith("(should be none): []")
    assert any(ln.startswith("[window] step shapes") and ln.endswith(": []")
               for ln in lines)
    metrics = out["metrics"]
    e2e = {m["name"] for m in M["end_to_end"] if "workloads" not in m}
    assert e2e <= set(metrics)
    # documents of 64-128 tokens (whole pages of 8) behind questions of
    # 8-24: most of what the tail's requests ask is served from the cache
    assert 70 < metrics["kv.prefix_hit_tokens_pct"]["value"] < 95
    # 2 of 32 experts held, top 2 of 32, 5-6 rows: a third of a token an
    # expert, so a step touches under one of the two
    assert 0 < metrics["moe.held_experts_touched_per_step"]["value"] <= 2
    # what reads a device trace has nothing to read on the CPU
    assert not {m["name"] for m in M["per_layer"]
                if m["source"] == "device_trace"} & set(metrics)
    assert metrics["runner.compiles_in_window"]["value"] == 0
    assert metrics["sched.preemptions"]["value"] == 0
    # the server's start-up line: weights, pool, the prefix cache
    with open(os.path.join(_paths.ROOT, "chiprun_out", "perfbench", CELL,
                           "server.log"), errors="replace") as f:
        said = [ln for ln in f if "[startup] latent model:" in ln]
    assert len(said) == 1, said
    assert "2 of 32 routed experts a layer held here" in said[0]
    assert "512 pages of 8 tokens x 5 layers x 128 stored lanes" in said[0]
    assert "prefix cache on" in said[0]
