"""The documents name only what exists: every repo-relative ``*.py`` /
``*.sh`` / ``*.conf`` path and every ``make <target>`` that README.md,
docs/*.md, the Makefile and the CI workflow mention must be in the tree.
A deleted script or target that a document still tells a reader to run
fails here, not in the reader's shell.
"""

import itertools
import os
import pathlib
import re

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent

DOCS = ["README.md", "docs/serving.md", "docs/observability.md",
        "docs/static_analysis.md", "Makefile", ".github/workflows/ci.yml"]

# a path-like token ending in a script/conf suffix; braces are the
# docs' shorthand for several files (``utils/{locktrace,shared}.py``)
_PATH = re.compile(r"(?<![\w./{}*<>$-])([\w./{},-]*\w\.(?:py|sh|conf))\b")
# ``make <target>`` as a command: in backticks, at the start of a
# (comment) line, or behind ``timeout N`` — never mid-sentence prose
_MAKE = re.compile(r"(?:`|^[ \t]*#?[ \t]*|timeout \d+ )make ([a-z][\w-]*)",
                   re.MULTILINE)
# where the documents' relative paths are rooted
_ROOTS = ["", "difacto_tpu", "tools", "tests", "perfbench", "docs"]


def _basenames():
    """File names in the tree, scratch and hidden directories left out
    (no git here: the checkout under test may not be a repository)."""
    names = set()
    for _, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith((".", "__"))
                   and d != "chiprun_out"]
        names.update(files)
    return names


def _expand(token):
    """``a/{b,c}.py`` -> ``a/b.py``, ``a/c.py``."""
    parts = re.split(r"\{([^{}]*)\}", token)
    choices = [p.split(",") if i % 2 else [p] for i, p in enumerate(parts)]
    return ["".join(c) for c in itertools.product(*choices)]


def _exists(path, basenames):
    if "/" not in path:
        # a bare file name speaks of a file of the paragraph's module
        return path in basenames
    return any((REPO / root / path).is_file() for root in _ROOTS)


def _makefile_targets():
    text = (REPO / "Makefile").read_text()
    return set(re.findall(r"^([a-z][\w-]*):", text, re.MULTILINE))


@pytest.mark.parametrize("doc", DOCS)
def test_document_names_only_what_exists(doc):
    text = (REPO / doc).read_text()
    basenames = _basenames()
    paths = {p for tok in _PATH.findall(text) for p in _expand(tok)
             # the reference implementation's tree, cited by file:line
             if not p.startswith(("src/", "/", "~"))}
    assert paths, f"{doc} names no file: the pattern has rotted"
    missing = sorted(p for p in paths if not _exists(p, basenames))
    assert not missing, f"{doc} names files that do not exist: {missing}"
    targets = set(_MAKE.findall(text))
    gone = sorted(targets - _makefile_targets())
    assert not gone, f"{doc} names make targets that do not exist: {gone}"
