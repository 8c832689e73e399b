"""run.py end to end on the CPU for the latent-attention cell, at the
configuration's rehearsal widths (both layer kinds, a window of 13 and a
top-k of 24 under contexts of 60-140 tokens, 4 of 32 experts held). The
cell, its configuration, its traffic mix, its reference, its kernels and
its metrics were added by files alone. (The lower-precision control at
these widths is tests/perfbench/test_reference_dots3.py's: a served side
in int8 against the same comparison.)"""

import json
import os
import subprocess
import sys

import _paths

RUN = os.path.join(_paths.BENCH, "run.py")
CELL = "dots3-note-prev.longdoc"
M = _paths.manifest()


def bench(*args, timeout=900):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    r = subprocess.run([sys.executable, RUN, "--workload", CELL,
                        "--cpu-rehearsal", *args], cwd=_paths.ROOT, env=env,
                       text=True, capture_output=True, timeout=timeout)
    return r.returncode, r.stdout.strip().splitlines(), r.stderr


def test_traced_rehearsal_is_correct_meets_no_new_shape_and_reads_the_counters():
    rc, lines, err = bench("--seed", str(2 ** 31 + 142), "--seconds", "4",
                           "--trace", "2")
    assert rc == 0, (lines[-15:], err[-2000:])
    out = json.loads(lines[-1])
    assert set(out) == {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 10
    assert any("prefill_rel_rms" in ln and "limit" in ln for ln in lines)
    assert any("decode_rel_rms" in ln and "limit" in ln for ln in lines)
    fill = [ln for ln in lines if ln.startswith("[fill] over")]
    assert len(fill) == 1 and fill[0].endswith("(should be none): []")
    assert any(ln.startswith("[window] step shapes") and ln.endswith(": []")
               for ln in lines)
    metrics = out["metrics"]
    e2e = {m["name"] for m in M["end_to_end"] if "workloads" not in m}
    assert e2e <= set(metrics)
    # a closed loop of 12 callers on 16 rings (a caller between two
    # requests holds none)
    assert 50 <= metrics["kv.window_store_peak_pct"]["value"] <= 100
    # 4 of 32 experts held, top 2 of 32: an eighth of the assignments in
    # expectation (a few thousand of them in the tail)
    assert 8 < metrics["moe.held_assignments_pct"]["value"] < 18
    # 11-12 rows x 2 / 32 experts: 0.7 tokens an expert, about half of the
    # four held experts touched
    assert 0.5 < metrics["moe.experts_touched_per_step"]["value"] <= 4
    # contexts of 60-140 tokens against a top-k of 24
    assert 15 < metrics["dsa.chosen_of_visible_pct"]["value"] < 60
    # what reads a device trace has nothing to read on the CPU
    assert not {m["name"] for m in M["per_layer"]
                if m["source"] == "device_trace"} & set(metrics)
    assert metrics["runner.compiles_in_window"]["value"] == 0
