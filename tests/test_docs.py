"""The documents name files that exist.

One case a document over ``README.md``, ``PERF.md`` and ``docs/*.md``.
Of every backticked token, each word — a trailing ``::symbol`` or
``:line`` cut off, globs expanded — is held to three rules:

(i)   a path that starts with one of the repo's top-level directories
      names a file or a directory that exists;
(ii)  a bare ``*.py`` / ``*.sh`` name is the basename of some file of
      the repo;
(iii) a bare upper-case-led record name (``PERF_LEDGER.jsonl``,
      ``BENCHMARK.json``, ``ROADMAP.md`` ...) exists at the root.

Words with a placeholder (``<...>``, ``{...}``, ``$...``, an ellipsis)
are skipped, and lower-case bare ``*.json`` names (an upstream
``config.json``) are not checked. A document that names a file on
purpose as gone says so without the backticks: there is no list of
exceptions here.
"""

import fnmatch
import functools
import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOP_DIRS = ("byteps_tpu", "benchmark", "tests", "examples", "scripts",
            "docs", "launcher")
DOCS = ["README.md", "PERF.md"] + sorted(
    os.path.relpath(p, ROOT)
    for p in glob.glob(os.path.join(ROOT, "docs", "*.md")))

_FENCE = re.compile(r"^```.*?^```", re.S | re.M)
_TOKEN = re.compile(r"`+([^`\n]+)`+")
_SUFFIX = re.compile(r"(::[\w.:]+|:[\d,\-–]+)$")
_PLACEHOLDER = re.compile(r"[<>{}$…]|\.\.\.")
_RECORD = re.compile(r"[A-Z][\w*?\[\]\-]*\.(md|json|jsonl|txt)")
_SCRIPT = re.compile(r"[\w*?\[\]\-]+\.(py|sh)")


@functools.lru_cache(maxsize=None)
def _basenames():
    names = set(os.listdir(ROOT))
    for top in TOP_DIRS:
        for _, dirs, files in os.walk(os.path.join(ROOT, top)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            names.update(files)
    return names


def _words(text):
    for token in _TOKEN.findall(_FENCE.sub("", text)):
        for word in token.split():
            word = _SUFFIX.sub("", word.strip("()[],;\"'")).rstrip(".:,")
            if word and not _PLACEHOLDER.search(word):
                yield word


def _dangling(text, basenames):
    missing = set()
    for word in _words(text):
        if "/" in word:
            if word.split("/", 1)[0] not in TOP_DIRS:
                continue
            found = glob.glob(os.path.join(ROOT, word))  # (i)
        elif _SCRIPT.fullmatch(word):
            found = [n for n in basenames
                     if fnmatch.fnmatchcase(n, word)]  # (ii)
        elif _RECORD.fullmatch(word):
            found = glob.glob(os.path.join(ROOT, word))  # (iii)
        else:
            continue
        if not found:
            missing.add(word)
    return sorted(missing)


@pytest.mark.parametrize("doc", DOCS)
def test_docs_name_files_that_exist(doc):
    with open(os.path.join(ROOT, doc), encoding="utf-8") as f:
        text = f.read()
    assert _dangling(text, _basenames()) == [], doc
