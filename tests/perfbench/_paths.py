"""Shared by the benchmark's tests: the benchmark's modules are files under
perfbench/, imported the way run.py imports them."""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "perfbench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


def bench_json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)
