"""The same --seed gives the same arrivals, lengths and tokens; another
seed gives the same sizes and gaps in another order."""

import random

import pytest

import _paths
from lib import dist
from run import load_module

CHAT = _paths.bench_json("traffic", "chat.json")
REASON = _paths.bench_json("traffic", "reason.json")
BIG_SEED = 2 ** 31 + 12345          # more than 32 signed bits hold


def chat_plan(seed, rate=4.0, seconds=30):
    gen = load_module("generators", "open_loop")
    return gen.plan(CHAT, {"rate_rps": rate}, seed, seconds, 151936)


def reason_plan(seed, clients=8):
    gen = load_module("generators", "closed_loop")
    return gen.plan(REASON, {"clients": clients}, seed, 30, 151936)


def key(reqs):
    return [(r.due, r.client, tuple(r.prompt), r.max_tokens) for r in reqs]


@pytest.mark.parametrize("plan", [chat_plan, reason_plan])
def test_same_seed_same_plan(plan):
    assert key(plan(BIG_SEED)) == key(plan(BIG_SEED))


@pytest.mark.parametrize("plan", [chat_plan, reason_plan])
def test_other_seed_other_order_same_sizes(plan):
    a, b = plan(BIG_SEED), plan(7)
    assert key(a) != key(b)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt)
                                                      for r in b)
    assert sorted(r.max_tokens for r in a) == sorted(r.max_tokens for r in b)
    assert [r.prompt for r in a] != [r.prompt for r in b]


def test_open_loop_offers_its_rate_and_spans_the_run_exactly():
    reqs = chat_plan(3, rate=4.0, seconds=30)
    ramp = CHAT["ramp_s"]
    assert len(reqs) == 4 * (30 + ramp)
    dues = [r.due for r in reqs]
    assert dues == sorted(dues) and dues[0] >= -ramp and dues[-1] < 30
    gaps = sorted(b - a for a, b in zip([-ramp] + dues, dues))
    gaps2 = sorted(b - a for a, b in zip(
        [-ramp] + [r.due for r in chat_plan(4)], [r.due for r in
                                                  chat_plan(4)]))
    assert gaps[:-1] == pytest.approx(gaps2[:-1])


def test_chat_lengths_follow_the_mix():
    reqs = chat_plan(5, rate=8.0, seconds=60)
    prompts = sorted(len(r.prompt) for r in reqs)
    outs = sorted(r.max_tokens for r in reqs)
    assert prompts[0] >= 32 and prompts[-1] <= 3072
    assert outs[0] >= 16 and outs[-1] <= 768
    assert 380 <= prompts[len(prompts) // 2] <= 420     # median 400
    assert 150 <= outs[len(outs) // 2] <= 170           # median 160
    assert 550 <= sum(prompts) / len(prompts) <= 650    # mean about 600
    assert all(2 <= t < 151936 for r in reqs[:20] for t in r.prompt)


def test_closed_loop_cuts_each_clients_first_request_only():
    reqs = reason_plan(11, clients=8)
    per = REASON["requests_per_client"]
    assert len(reqs) == 8 * per
    firsts = [r for i, r in enumerate(reqs) if i % per == 0]
    rest = [r for i, r in enumerate(reqs) if i % per]
    assert all(768 <= r.max_tokens <= 1536 for r in rest)
    assert all(128 <= len(r.prompt) <= 512 for r in rest)
    # every joining step of the fill has the same tokens and pages bucket
    assert {len(r.prompt) for r in firsts} == {520}
    cuts = sorted(r.max_tokens for r in firsts)
    assert cuts[0] >= 320 and cuts[-1] > 1400   # spread, and none short
    assert cuts == sorted(r.max_tokens for i, r in
                          enumerate(reason_plan(12, clients=8)) if i % per == 0)
    assert {r.client for r in reqs} == set(range(8))


def steps_of(reqs, per_join, gap, horizon):
    """The plan in steps: a caller joins every ``per_join`` steps, every
    stream gains a token a step, a caller is away ``gap`` steps between two
    requests. Per step: rows, KV pages of 16 tokens, the longest context."""
    rows, pages, longest = ([0] * horizon for _ in range(3))
    by_client = {}
    for r in reqs:
        by_client.setdefault(r.client, []).append(r)
    first_end = horizon
    for client in sorted(by_client):
        t = client * per_join
        for k, r in enumerate(by_client[client]):
            if k == 0:
                first_end = min(first_end, t + r.max_tokens)
            for i in range(max(0, min(r.max_tokens, horizon - t))):
                ctx = len(r.prompt) + i
                rows[t + i] += 1
                pages[t + i] += -(-ctx // 16)
                longest[t + i] = max(longest[t + i], ctx)
            t += r.max_tokens + gap
    return rows, pages, longest, first_end


@pytest.mark.parametrize("seed", [BIG_SEED, 7, 1000003, 424243, 31337])
@pytest.mark.parametrize("per_join,gap", [(3, 2), (8, 4)])
def test_the_reason_cell_stays_inside_the_shapes_its_warm_up_builds(
        seed, per_join, gap):
    """What cells/qwen3-4b.reason.json says of itself (``clients_from``,
    ``warmup_why``), held against the plan: the rows stay in one bucket,
    the pool never fills, nobody finishes inside the fill, and the pages
    bucket is 64 until a context passes 1024 tokens and 128 from then on
    (both are warmed; the window opens behind the change)."""
    cell = _paths.bench_json("cells", "qwen3-4b.reason.json")
    gen = load_module("generators", "closed_loop")
    reqs = gen.plan(REASON, cell, seed, 45, 151936)
    horizon, clients = 3800, cell["clients"]
    rows, pages, longest, first_end = steps_of(reqs, per_join, gap, horizon)
    fill_end = (clients - 1) * per_join
    assert first_end > fill_end + 50
    assert all(16 < n <= 32 for n in rows[fill_end:])       # row bucket 32
    assert max(pages) < 0.92 * 2800                         # pages on the v5e
    assert all(512 < c <= 2048 for c in longest)            # buckets 64, 128
    change = next(i for i, c in enumerate(longest) if c > 1024)
    assert change < 560         # ~22 s of steps: behind ramp_s = 35 s
    assert sum(c <= 1024 for c in longest[change:]) < 40    # rare, and warmed


@pytest.mark.parametrize("spec,lo,hi", [
    ({"dist": "uniform", "min": 10, "max": 20}, 10, 20),
    ({"dist": "lognormal", "median": 100, "sigma": 1.0, "min": 5,
      "max": 900}, 5, 900),
    ({"dist": "fixed", "value": 12}, 12, 12)])
def test_stratified_is_the_same_multiset_for_every_seed(spec, lo, hi):
    a = dist.stratified(spec, 50, random.Random(1))
    b = dist.stratified(spec, 50, random.Random(2))
    assert sorted(a) == sorted(b) and lo <= min(a) and max(a) <= hi


def test_unknown_distribution_is_an_error():
    with pytest.raises(ValueError):
        dist.quantile({"dist": "zipf"}, 0.5)
