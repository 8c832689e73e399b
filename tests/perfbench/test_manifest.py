"""BENCHMARK.json against its contract, and every name against its file."""

import os
import re

import pytest

import _paths

M = _paths.manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = {w["name"]: w for w in M["workloads"]}
E2E = {m["name"]: m for m in M["end_to_end"]}


def reporting(metric):
    """The cells that report ``metric``."""
    return set(metric.get("workloads", CELLS))


def test_top_level_keys_are_exactly_the_contracts():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer",
                      "trace_in_run"}
    # one run measures first and traces afterwards (run.py --trace 2)
    assert M["trace_in_run"] is True
    assert M["command"] == ["python3", "perfbench/run.py"]
    assert M["paths"] == ["perfbench", "tests/perfbench"]
    assert isinstance(M["run_seconds"], int) and 1 <= M["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(_paths.ROOT,
                                        "BENCHMARK.json")) <= 64 * 1024


def test_a_full_check_of_24_cells_fits_its_allowance():
    runs = 2 + 14 * 24
    total = runs * (M["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


@pytest.mark.parametrize("m", M["end_to_end"] + M["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entry(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in SOURCES
    assert reporting(m) <= set(CELLS)
    if m["name"] in E2E:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    else:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        # every cell that reports the layer metric reports what it moves
        assert m["moves"] in E2E
        assert reporting(m) <= reporting(E2E[m["moves"]])
        assert os.path.isfile(os.path.join(
            _paths.BENCH, "layer_metrics", m["name"] + ".py"))


def test_names_are_unique_and_setup_s_is_there():
    names = [m["name"] for m in M["end_to_end"] + M["per_layer"]]
    assert len(names) == len(set(names))
    assert E2E["setup_s"]["bound"] <= 0.1
    assert "workloads" not in E2E["setup_s"]
    assert 1 <= len(M["end_to_end"]) <= 16 and len(M["per_layer"]) <= 128


@pytest.mark.parametrize("w", M["workloads"], ids=lambda w: w["name"])
def test_cell_entry_and_its_files(w):
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert NAME.match(w[key])
    assert w["chips"] in (1, 4)
    assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    cell = _paths.bench_json("cells", w["name"] + ".json")
    assert (cell["config"], cell["traffic"], cell["chips"], cell["why"]) == (
        w["config"], w["traffic"], w["chips"], w["why"])
    traffic = _paths.bench_json("traffic", w["traffic"] + ".json")
    assert os.path.isfile(os.path.join(
        _paths.BENCH, "generators", traffic["generator"] + ".py"))
    assert w["config"] in {c["name"] for c in M["configs"]}
    # every cell reports setup_s, another end-to-end metric and a layer's
    assert sum(w["name"] in reporting(m) for m in M["end_to_end"]) >= 2
    assert any(w["name"] in reporting(m) for m in M["per_layer"])


def test_at_most_a_quarter_of_the_cells_or_one_take_four_chips():
    four = sum(w["chips"] == 4 for w in M["workloads"])
    assert four <= max(1, len(M["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(pairs) == len(set(pairs))


WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|"
                   r"_rank$|head_dim|expansion|experts_per_tok")


@pytest.mark.parametrize("c", M["configs"], ids=lambda c: c["name"])
def test_configuration_entry_and_its_file(c):
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(c["name"]) and c["source"].startswith("https://")
    assert c["file"] == f"perfbench/configs/{c['name']}.json"
    assert any(w["config"] == c["name"] for w in M["workloads"])
    conf = _paths.bench_json("configs", c["name"] + ".json")
    assert conf["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
    assert conf["source"] == c["source"]
    assert not any(WIDTH.search(k) for k in c["reduced"])
    assert os.path.isfile(os.path.join(
        _paths.BENCH, "reference", conf["reference"] + ".py"))
    fast = {"--overlap-scheduling", "--pipelined-loop", "--unified-step",
            "--decode-slot-batching", "--ondevice-finish",
            "--decode-chain-len", "--spec-decode", "--spec-fused",
            "--chain-under-prefill", "--enable-prefix-caching",
            "--quantization", "--kv-cache-dtype"}
    assert not fast & set(conf["server_flags"])       # no fast-path flag


def test_published_widths_of_qwen3_4b():
    conf = _paths.bench_json("configs", "qwen3-4b.json")
    published = dict(hidden_size=2560, num_hidden_layers=36,
                     num_attention_heads=32, num_key_value_heads=8,
                     head_dim=128, intermediate_size=9728,
                     vocab_size=151936, tie_word_embeddings=True,
                     rope_theta=1000000, rms_norm_eps=1e-6)
    assert {k: conf[k] for k in published} == published
