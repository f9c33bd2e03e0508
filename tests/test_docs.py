"""The documents cite only files that exist.

Every backticked word that names a file under a directory (it holds a
``/`` and ends in ``.py``, ``.json``, ``.jsonl``, ``.md`` or ``.sh``) must
resolve against the repo root, ``src/repro/`` or ``src/``, after any
``:name`` / ``:line`` / ``::test`` suffix is stripped; every bare
``UPPERCASE.md`` name must be a file at the root.  Patterns (``*``, ``<``,
``{``) and absolute paths are not citations.  The markdown documents are
checked whole; the Python sources only in their docstrings and comments,
where a citation is prose.
"""
import ast
import glob
import io
import os
import re
import tokenize

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASES = ("", "src/repro", "src")
EXTENSIONS = (".py", ".json", ".jsonl", ".md", ".sh")

_BACKTICKED = re.compile(r"`+([^`]+?)`+")
_BARE_MD = re.compile(r"(?<![\w/.-])([A-Z][A-Z0-9_]*\.md)\b")


def _cited_paths(text):
    """The path citations in ``text``: (kind, token) pairs."""
    for span in _BACKTICKED.findall(text):
        for word in span.split():
            word = word.strip("()[],;'\"").split("=")[-1]
            path = word.split(":")[0]
            if ("/" in path and path.endswith(EXTENSIONS)
                    and not any(c in path for c in "*<{")
                    and not path.startswith(("/", "~"))):
                yield "path", path
    for name in _BARE_MD.findall(text):
        yield "root", name


def _missing(text):
    out = []
    for kind, token in _cited_paths(text):
        bases = BASES if kind == "path" else ("",)
        if not any(os.path.isfile(os.path.join(ROOT, b, token))
                   for b in bases):
            out.append(token)
    return out


def _prose(source):
    """The docstrings and comments of one Python source, joined."""
    parts = []
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                             ast.AsyncFunctionDef)):
            doc = ast.get_docstring(node, clean=False)
            if doc:
                parts.append(doc)
    for tok in tokenize.generate_tokens(io.StringIO(source).readline):
        if tok.type == tokenize.COMMENT:
            parts.append(tok.string)
    return "\n".join(parts)


DOCUMENTS = ["README.md", "ROADMAP.md", "PERF.md",
             ".claude/skills/verify/SKILL.md"]


@pytest.mark.parametrize("where", DOCUMENTS + ["src/repro/**/*.py"])
def test_cited_paths_exist(where):
    files = sorted(glob.glob(os.path.join(ROOT, where), recursive=True))
    assert files, where
    missing = {}
    for path in files:
        with open(path) as f:
            text = f.read()
        if path.endswith(".py"):
            text = _prose(text)
        bad = _missing(text)
        if bad:
            missing[os.path.relpath(path, ROOT)] = sorted(set(bad))
    assert not missing, missing


def test_cited_paths_sees_both_kinds_of_citation():
    text = ("see `core/sort.py:make_sort_fn`, ``tests/test_api.py::t``, "
            "`PYTHONPATH=src python examples/quickstart.py`, `bench/*.py`, "
            "`/abs/x.py`, `a/b.txt`, PERF.md and .claude/skills/verify/SKILL.md")
    assert list(_cited_paths(text)) == [
        ("path", "core/sort.py"), ("path", "tests/test_api.py"),
        ("path", "examples/quickstart.py"), ("root", "PERF.md")]
    assert _missing("`kernels/nope.py` and DESIGN.md") == [
        "kernels/nope.py", "DESIGN.md"]
    assert _prose('"""`a/b.py`"""\nx = "`c/d.py`"  # `e/f.py`\n') == (
        "`a/b.py`\n# `e/f.py`")
