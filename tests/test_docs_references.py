"""Document-reference guard: a document cannot name what the tree lacks.

For the README, PARITY.md, the verify skill and each ``docs/*.md``:
every repo-relative path the text names exists, every ``python -m``
module of this repo it tells the reader to run imports from a file that
exists, and every ``--flag`` it names is taken by an argument parser of
the repo (the server's is built for real; the other entry points' are
found by source scan, as tests/test_metrics_catalog.py finds metrics).

It fails where a document still teaches a script, a record or a lever
that a PR deleted (ISSUE 29: ``bench.py`` and its ``--tiny`` /
``--inner`` arms, the pre-growth timing scripts and records).
"""

import pathlib
import re

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
DOCS = ["README.md", "PARITY.md", ".claude/skills/verify/SKILL.md"] + sorted(
    str(p.relative_to(REPO)) for p in (REPO / "docs").glob("*.md"))

# where a name a document gives without its root is looked for
BASES = ("", "gllm_tpu", "docs", "tests", "benchmarks", "perfbench")
# trees whose files a bare ``name.py`` may mean
CODE_ROOTS = ("gllm_tpu", "benchmarks", "perfbench", "tests", "examples")
# written by a run, or placeholders of a usage line
_RUNTIME = ("chiprun_out/", "tmp/", "path/")
# files of the reference snapshot (/root/reference/gllm) that a document
# cites by line beside the module that took their place
_REFERENCE_FILES = {"comm.py", "dist_utils.py", "fp8.py"}

_PATH_RE = re.compile(r"(?<![\w/.\-~])((?:[\w\-]+/)*[\w\-]+\.(?:py|md|json))\b")
_MODULE_RE = re.compile(r"python3? +(?:-\w+ +)*-m +([\w.]+)")
_FLAG_RE = re.compile(r"(?<![\w\-])(--[a-z][a-z0-9\-]*[a-z0-9])(?![\w])")
_ADD_ARG_RE = re.compile(
    r"add_argument\(\s*((?:['\"]--?[\w\-]+['\"]\s*,?\s*)+)")

# flags of tools that are not this repo's: pytest, the chip tool
_FOREIGN_FLAGS = {"--collect-only", "--timeout", "--status"}


def _text(doc: str) -> str:
    text = (REPO / doc).read_text()
    if doc == "PARITY.md":
        # the first column of its tables names the REFERENCE's files
        text = "\n".join(
            line.split("|", 2)[2] if line.startswith("|") else line
            for line in text.splitlines())
    return text


def _missing_paths(doc: str, text: str, basenames: set) -> list:
    here = (REPO / doc).parent
    missing = []
    for name in sorted(set(_PATH_RE.findall(text))):
        if name.startswith(_RUNTIME) or name in _REFERENCE_FILES:
            continue
        if any((REPO / base / name).exists() for base in BASES) \
                or (here / name).exists():
            continue
        if "/" not in name:
            if name.endswith(".py") and name in basenames:
                continue
            if not name.endswith(".py") and not name[0].isupper():
                continue    # an output or a checkpoint's file (t.json)
        missing.append(name)
    return missing


def _missing_modules(text: str) -> list:
    missing = []
    for mod in sorted(set(_MODULE_RE.findall(text))):
        if mod.split(".")[0] not in ("gllm_tpu", "perfbench", "benchmarks"):
            continue
        path = REPO.joinpath(*mod.split("."))
        if not (path.with_suffix(".py").exists()
                or (path / "__init__.py").exists()):
            missing.append(mod)
    return missing


@pytest.fixture(scope="module")
def tree():
    """(file names a bare ``name.py`` may mean, flags some parser takes)."""
    from gllm_tpu.entrypoints.api_server import make_parser
    flags = {s for a in make_parser()._actions for s in a.option_strings}
    files = sorted(REPO.glob("*.py"))
    for root in CODE_ROOTS:
        files += sorted((REPO / root).rglob("*.py"))
    for f in files:
        for args in _ADD_ARG_RE.findall(f.read_text()):
            flags |= set(re.findall(r"['\"](--[a-z0-9\-]+)['\"]", args))
    return {f.name for f in files}, flags | _FOREIGN_FLAGS


@pytest.mark.parametrize("doc", DOCS)
def test_a_document_names_only_what_exists(doc, tree):
    basenames, known_flags = tree
    text = _text(doc)
    paths = _missing_paths(doc, text, basenames)
    assert not paths, f"{doc} names files the tree does not have: {paths}"
    modules = _missing_modules(text)
    assert not modules, f"{doc} runs modules that do not exist: {modules}"
    # ``--mm-processor-min/max-pixels`` style: a prefix of a real flag
    flags = [f for f in sorted(set(_FLAG_RE.findall(text)))
             if not any(k.startswith(f) for k in known_flags)]
    assert not flags, f"{doc} names flags no parser of the repo takes: {flags}"
