"""The documents name only paths that exist.

Every later reader starts from `README.md` and `docs/`; a deleted file
that a document still cites sends them to nothing. One case a document:
each repository path it names in backticks must exist. `CHANGES.md`,
`ROADMAP.md` and `PERF.md` are history and queues and are left out.
"""

import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md", "PARITY.md", "BASELINE.md"] + sorted(
    os.path.join("docs", f) for f in os.listdir(os.path.join(ROOT, "docs"))
    if f.endswith(".md"))

_CODE = re.compile(r"`([^`\n]+)`")
_LINES = re.compile(r":\d+([-–,]\d+)*$")
_PATH_END = re.compile(r"(\.py|\.json|\.md|/)$")
#: globs, placeholders, command lines, URLs and paths outside the checkout
_NOT_A_PATH = re.compile(r"[*<>{}=\s…]|\.\.\.|://|^/|^~")


def paths_named(text):
    """The repository paths a document names in backticks: a token that
    ends in `.py`, `.json`, `.md` or `/`, its `:line` suffix stripped."""
    out = []
    for tok in _CODE.findall(text):
        tok = _LINES.sub("", tok.strip())
        if not _PATH_END.search(tok) or _NOT_A_PATH.search(tok):
            continue
        if "/" not in tok and tok[0] in ".-":
            continue    # an extension, an option, a file made at run time
        out.append(tok)
    return out


@pytest.fixture(scope="module")
def base_names():
    """Base names of every file in the checkout (hidden directories and
    what a run leaves behind are not the repository)."""
    names = set()
    for _, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("__pycache__", "chiprun_out")]
        names.update(files)
    return names


def _exists(doc, tok, base_names):
    if "/" not in tok:
        # a bare file name stands for the module the sentence is about
        return tok in base_names
    return any(os.path.exists(os.path.join(ROOT, base, tok))
               for base in ("", "nnstreamer_tpu", os.path.dirname(doc)))


def test_paths_named_rule():
    text = ("`serving/lm_engine.py:529–532` and `gone.py`, `docs/`, "
            "`tests/test_*.py`, `configs/<name>.json`, `.py`, "
            "`python3 benchmark/run.py`, `/root/reference/README.md`, "
            "`LMEngine`, `PERF.md:12`")
    assert paths_named(text) == [
        "serving/lm_engine.py", "gone.py", "docs/", "PERF.md"]


@pytest.mark.parametrize("doc", DOCS)
def test_document_names_only_paths_that_exist(doc, base_names):
    with open(os.path.join(ROOT, doc), encoding="utf-8") as f:
        named = paths_named(f.read())
    assert named, f"{doc} names no path: the rule reads nothing"
    missing = sorted({t for t in named if not _exists(doc, t, base_names)})
    assert not missing, f"{doc} names paths that do not exist: {missing}"


@pytest.mark.parametrize("name", ["benchmark/run.py", "BENCHMARK.json",
                                  "PERF.md", "PERF_LEDGER.jsonl"])
def test_readme_sends_a_reader_to_the_benchmark(name):
    with open(os.path.join(ROOT, "README.md"), encoding="utf-8") as f:
        assert any(name in tok for tok in _CODE.findall(f.read()))
