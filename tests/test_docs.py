"""The documents name things that exist: every ``python <path>``, ``bash
<path>`` and ``tools/…``, ``examples/…``, ``tests/…`` path in a code span or
block of a user document resolves to a file in the tree, and every console
script a command starts with is one ``pyproject.toml`` installs."""

import glob
import itertools
import os
import re
import tomllib

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md",
        *sorted(os.path.relpath(p, ROOT)
                for p in glob.glob(os.path.join(ROOT, "docs", "*.md"))),
        os.path.join("benchmark", "README.md")]

CODE = re.compile(r"```.*?```|`[^`\n]+`", re.S)
#: a path under one of the three directories a reader is sent to
PATH = re.compile(r"(?<![\w/.<>-])((?:tools|examples|tests)/[\w./*{},-]*[\w*}])")
#: the script an interpreter is given (``python -m``, ``-c`` and the
#: placeholders ``<script>`` are not paths)
COMMAND = re.compile(r"\b(?:python3?|bash)\s+((?![-/])[\w./-]+\.(?:py|sh))\b")
#: a launcher of ours at the start of a command (after ``VAR=value`` words)
LAUNCHER = re.compile(r"(?:^|\n)\s*(?:\$\s+)?(?:[A-Z][A-Z0-9_]*=\S+\s+)*"
                      r"(dl[a-z]+)(?![\w./-])")


def _expand(path: str) -> list[str]:
    """``train_{a,b}.py`` -> both; anything else -> itself."""
    m = re.search(r"\{([^{}]*)\}", path)
    if not m:
        return [path]
    return list(itertools.chain.from_iterable(
        _expand(path[:m.start()] + alt + path[m.end():])
        for alt in m.group(1).split(",")))


def _resolves(path: str, doc_dir: str) -> bool:
    return any(glob.glob(os.path.join(base, p))
               for p in _expand(path) for base in (ROOT, doc_dir))


def test_there_are_eight_documents():
    assert len(DOCS) == 8 and all(
        os.path.isfile(os.path.join(ROOT, d)) for d in DOCS), DOCS


@pytest.mark.parametrize("doc", DOCS)
def test_the_document_names_things_that_exist(doc):
    with open(os.path.join(ROOT, doc)) as f:
        code = "\n".join(CODE.findall(f.read()))
    with open(os.path.join(ROOT, "pyproject.toml"), "rb") as f:
        scripts = set(tomllib.load(f)["project"]["scripts"])
    doc_dir = os.path.dirname(os.path.join(ROOT, doc))
    named = set(PATH.findall(code)) | set(COMMAND.findall(code))
    missing = sorted(p for p in named if not _resolves(p, doc_dir))
    assert not missing, f"{doc} names files that are not in the tree: {missing}"
    unknown = sorted(set(LAUNCHER.findall(code)) - scripts)
    assert not unknown, (f"{doc} names console scripts that pyproject.toml "
                         f"does not install: {unknown}")
