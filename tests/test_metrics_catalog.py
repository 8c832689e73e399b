"""Metrics-catalog guard: the code and docs/observability.md cannot
drift.

Every ``gllm_*`` metric registered anywhere under ``gllm_tpu/`` (via the
``obs.counter/gauge/histogram`` helpers) must have a row in
docs/observability.md, and every ``gllm_*`` name the doc mentions must
be a registered metric (or a histogram's derived ``_bucket``/``_sum``/
``_count`` sample, or a documented-retired alias) — so a new subsystem
can't ship undocumented metrics and the doc can't advertise ghosts.

Registration sites are found by source scan rather than imports: it
covers modules that only load under flags/topologies CI never runs
(pp_runner, disagg, the kvstore tiers), and it needs no jax.
"""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "gllm_tpu")
DOC = os.path.join(REPO, "docs", "observability.md")

# obs.counter( / metrics.gauge( / histogram( ... "gllm_..." — the name
# is always the first (string-literal) argument.
_REG_RE = re.compile(
    r"\b(?:counter|gauge|histogram)\(\s*\n?\s*['\"](gllm_[a-z0-9_]+)['\"]",
    re.MULTILINE)
_DOC_RE = re.compile(r"\bgllm_[a-z0-9_]+")

# Histogram sample suffixes the doc legitimately shows as full series
# names in PromQL recipes / examples.
_HIST_SUFFIXES = ("_bucket", "_sum", "_count")


def _registered_names():
    names = {}
    for root, _, files in os.walk(PKG):
        if "__pycache__" in root:
            continue
        for fn in files:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(root, fn)
            src = open(path).read()
            for m in _REG_RE.finditer(src):
                names.setdefault(m.group(1), path)
    return names


def test_every_registered_metric_is_documented():
    registered = _registered_names()
    assert registered, "source scan found no metric registrations"
    doc = open(DOC).read()
    missing = sorted(n for n in registered if n not in doc)
    assert not missing, (
        "metrics registered in gllm_tpu/ but absent from "
        "docs/observability.md (add a catalog row): "
        + ", ".join(f"{n} ({os.path.relpath(registered[n], REPO)})"
                    for n in missing))


def test_every_documented_metric_is_registered():
    registered = set(_registered_names())
    doc = open(DOC).read()
    ghosts = []
    for name in sorted(set(_DOC_RE.findall(doc))):
        if name == "gllm_tpu":           # the package name, not a metric
            continue
        if name in registered:
            continue
        if any(name.endswith(s) and name[:-len(s)] in registered
               for s in _HIST_SUFFIXES):
            continue
        if any(r.startswith(name) for r in registered):
            continue                     # grep-prefix in a shell recipe
        ghosts.append(name)
    assert not ghosts, (
        "docs/observability.md mentions gllm_* names no code registers "
        "(typo or removed metric — fix the doc): " + ", ".join(ghosts))


# ---- steptrace event kinds / span phases (ISSUE 10 satellite) --------------
#
# Same no-drift contract for the trace vocabularies: every
# ``TRACE.record("<kind>", ...)`` call site in gllm_tpu/ must have a row
# in the doc's event-kind catalog (and vice versa), and every span phase
# a request tree may carry (spans.SPAN_PHASES; a literal
# ``SPANS.event(..., "<phase>", ...)`` call site must use one of them) a
# row in the span-phase catalog. The catalogs are marker-delimited tables
# so the doc can mention kind-words in prose without tripping the guard.

_TRACE_RE = re.compile(r"\bTRACE\.record\(\s*\n?\s*['\"]([a-z_]+)['\"]")
# SPANS.event(sid, "phase", ...) and the tracker-internal
# self._append_locked(rec, "phase", ...) of the roll-ups in spans.py.
# The children that come from a request's stamps (parse, intake, queued,
# handover, emit) are named by a table there, not by a call site.
_SPAN_RE = re.compile(
    r"\.(?:event|_append_locked)\(\s*\n?\s*[^,()]+,\s*\n?\s*"
    r"['\"]([a-z_]+)['\"]")


def _scan(regex):
    found = {}
    for root, _, files in os.walk(PKG):
        if "__pycache__" in root:
            continue
        for fn in files:
            if fn.endswith(".py"):
                path = os.path.join(root, fn)
                for m in regex.finditer(open(path).read()):
                    found.setdefault(m.group(1), path)
    return found


def _catalog(marker):
    doc = open(DOC).read()
    start = doc.index(f"<!-- {marker} -->")
    end = doc.index(f"<!-- /{marker} -->")
    return set(re.findall(r"^\|\s*`([a-z_]+)`",
                          doc[start:end], re.MULTILINE))


def test_every_trace_kind_is_documented_and_vice_versa():
    # The step kinds (prefill/decode/fused_block) are recorded through a
    # VARIABLE (engine/llm.py _record_step computes the kind), so the
    # declared taxonomy in steptrace.STEP_KINDS joins the literal call
    # sites as the authoritative "recorded" set.
    from gllm_tpu.obs.steptrace import STEP_KINDS
    recorded = _scan(_TRACE_RE)
    assert recorded, "source scan found no TRACE.record call sites"
    known = set(recorded) | set(STEP_KINDS)
    documented = _catalog("event-kind-catalog")
    missing = sorted(known - documented)
    assert not missing, (
        "TRACE.record kinds with no docs/observability.md event-kind-"
        "catalog row: "
        + ", ".join(f"{k} ({os.path.relpath(recorded[k], REPO)})"
                    if k in recorded else k for k in missing))
    ghosts = sorted(documented - known)
    assert not ghosts, (
        "event-kind-catalog rows no TRACE.record call site emits "
        f"(fix the doc): {ghosts}")
    stray = sorted(set(recorded) - set(STEP_KINDS))
    assert not stray, (
        "TRACE.record call sites using kinds absent from "
        f"steptrace.STEP_KINDS (extend the taxonomy): {stray}")


def test_every_span_phase_is_documented_and_vice_versa():
    from gllm_tpu.obs.spans import _TREE_CHILD, SPAN_PHASES
    recorded = _scan(_SPAN_RE)
    assert recorded, "source scan found no SPANS.event call sites"
    stray = sorted(set(recorded) - set(SPAN_PHASES))
    assert not stray, (
        "SPANS.event call sites using phases absent from "
        f"spans.SPAN_PHASES (extend the taxonomy): {stray}")
    # every phase of the taxonomy is written somewhere: by a call site,
    # or from a request's stamps
    unwritten = sorted(set(SPAN_PHASES) - set(recorded)
                       - set(_TREE_CHILD.values()))
    assert not unwritten, f"span phases nothing records: {unwritten}"
    documented = _catalog("span-phase-catalog")
    missing = sorted(set(SPAN_PHASES) - documented)
    assert not missing, (
        "span phases with no docs/observability.md span-phase-catalog "
        f"row: {missing}")
    ghosts = sorted(documented - set(SPAN_PHASES))
    assert not ghosts, (
        "span-phase-catalog rows outside spans.SPAN_PHASES "
        f"(fix the doc): {ghosts}")
    # the retired per-step children stay out of the package
    for name in ("decode_step", "decode_chain", "event_many"):
        assert name not in recorded
        assert name not in open(os.path.join(
            PKG, "obs", "spans.py")).read()


# ---- engine-loop phases (ISSUE 24) -----------------------------------------
#
# The closed vocabulary of obs/spans.phase: every ``phase("<name>"``
# call site in gllm_tpu/ uses a name of spans.ENGINE_PHASES (or the
# nested ``first_use``), every name of the vocabulary is opened
# somewhere, and the doc's engine-phase catalog has exactly these rows.

_PHASE_RE = re.compile(r"\bphase\(\s*\n?\s*['\"]([a-z_]+)['\"]")


def test_every_engine_phase_is_opened_documented_and_vice_versa():
    from gllm_tpu.obs.spans import ENGINE_PHASES, HOST_PHASES
    opened = _scan(_PHASE_RE)
    vocabulary = set(ENGINE_PHASES) | {"first_use"}
    stray = sorted(set(opened) - vocabulary)
    assert not stray, (
        "phase() call sites outside spans.ENGINE_PHASES (extend the "
        "vocabulary and the doc): "
        + ", ".join(f"{n} ({os.path.relpath(opened[n], REPO)})"
                    for n in stray))
    unopened = sorted(vocabulary - set(opened))
    assert not unopened, f"phases no call site opens: {unopened}"
    documented = _catalog("engine-phase-catalog")
    assert documented == vocabulary, (
        sorted(documented ^ vocabulary))
    assert set(HOST_PHASES) < set(ENGINE_PHASES)


# ---- retired names (ISSUE 29) ----------------------------------------------
#
# The engine loop's host-clock guesses at device numbers are gone: what
# the device did is read from a profiler capture (perfbench/host_gaps.py).
# None of their names may come back as a metric, a step-event field, a
# summary field or a line of the doc.

RETIRED = ("gllm_kv_bytes_read_total", "gllm_overlap_efficiency",
           "gllm_dispatch_rtt_seconds", "dev_ms", "rtt_ms", "bubble_frac",
           "overlap_efficiency", "device_ms_by_kind")


@pytest.fixture(scope="module")
def served():
    """What a short CPU run of the default engine leaves behind: the
    registry's exposition, the keys of its step events, and the keys of
    their summary."""
    from gllm_tpu.config import CacheConfig, EngineConfig, SchedulerConfig
    from gllm_tpu.engine.llm import LLM
    from gllm_tpu.models.config import ModelConfig
    from gllm_tpu.obs import metrics
    from gllm_tpu.obs.steptrace import TRACE, summarize
    from gllm_tpu.sampling_params import SamplingParams
    llm = LLM(
        config=EngineConfig(
            load_format="dummy", dtype="float32", max_model_len=64,
            max_num_seqs=4,
            scheduler=SchedulerConfig(max_prefill_tokens=32,
                                      max_decode_seqs=4),
            cache=CacheConfig(page_size=4, num_pages=32)),
        model_cfg=ModelConfig(
            architecture="LlamaForCausalLM", vocab_size=128,
            hidden_size=32, num_layers=2, num_heads=4, num_kv_heads=2,
            head_dim=8, intermediate_size=64, max_position=128))
    mark = TRACE.mark()
    llm.generate(prompt_token_ids=[[3, 5, 7], [2, 4]],
                 sampling_params=SamplingParams(max_tokens=4,
                                                temperature=0.0,
                                                ignore_eos=True))
    steps = [e for e in TRACE.events(since=mark) if "ph" in e]
    assert steps, "the run recorded no step event"
    keys = set().union(*steps)
    assert {"ph", "step_wall_ms", "wall_ms", "tokens", "inflight"} <= keys
    exposition = metrics.render()
    assert "gllm_steps_total" in exposition
    return {"exposition": exposition,
            "event keys": " ".join(sorted(keys)),
            "summary keys": " ".join(sorted(summarize(steps))),
            "docs/observability.md": open(DOC).read()}


@pytest.mark.parametrize("name", RETIRED)
def test_retired_names_stay_gone(served, name):
    hits = [where for where, text in served.items() if name in text]
    assert not hits, f"{name} is back in: {hits}"
