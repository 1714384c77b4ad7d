"""A document names files that exist.

Every backticked name in the README, ``CLAUDE.md``, the verify skill and
``docs/*.md`` that ends in a source, data or document suffix is one of:
a file of the tree (by its path from the root or the tail of it, down to
the bare file name), what a run leaves behind (a ``.gitignore`` pattern),
a path of the reference's layout (followed by a dagger, SURVEY.md's
convention), or a model card's bare ``config.json``. Nothing else is let through: a
document that sends its reader to a file that left fails here.
"""

import fnmatch
import functools
import glob
import os
import re

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_NAME = re.compile(
    r"`([^`\s]+\.(?:py|json|jsonl|md|toml|cpp))`(\s*(?:†|\(dagger\)))?")
_FENCE = re.compile(r"^```.*?^```", re.S | re.M)
DOCS = ["README.md", "CLAUDE.md", ".claude/skills/verify/SKILL.md"] + sorted(
    os.path.relpath(p, _ROOT)
    for p in glob.glob(os.path.join(_ROOT, "docs", "*.md")))


@functools.lru_cache(maxsize=None)
def _ignore_patterns():
    with open(os.path.join(_ROOT, ".gitignore")) as f:
        return tuple(ln.strip() for ln in f
                     if ln.strip() and not ln.startswith("#"))


def _ignored(rel: str, patterns) -> bool:
    parts = rel.split("/")
    for pat in patterns:
        if pat.endswith("/"):
            if pat.rstrip("/") in parts[:-1]:
                return True
        elif fnmatch.fnmatch(parts[-1], pat) or fnmatch.fnmatch(rel, pat):
            return True
    return False


@functools.lru_cache(maxsize=None)
def _tails():
    """Every tail of the path of every file of the tree (``a/b/c.py``,
    ``b/c.py``, ``c.py``), run output left out."""
    patterns = _ignore_patterns()
    paths = set()
    for top, dirs, files in os.walk(_ROOT):
        rel_top = os.path.relpath(top, _ROOT)
        dirs[:] = [d for d in dirs if d != ".git" and not _ignored(
            os.path.normpath(os.path.join(rel_top, d, "x")), patterns)]
        for name in files:
            rel = os.path.normpath(os.path.join(rel_top, name))
            if not _ignored(rel, patterns):
                paths.add(rel)
    return {"/".join(parts[i:]) for parts in (p.split("/") for p in paths)
            for i in range(len(parts))}


@pytest.mark.parametrize("doc", DOCS)
def test_a_document_names_files_that_exist(doc):
    patterns, tails = _ignore_patterns(), _tails()
    with open(os.path.join(_ROOT, doc)) as f:
        text = _FENCE.sub("", f.read())
    missing = sorted({
        name for name, dagger in _NAME.findall(text)
        if not (dagger or name == "config.json"
                or os.path.normpath(name) in tails
                or _ignored(name, patterns))
    })
    assert not missing, f"{doc} names files that are not in the tree: " \
                        f"{missing}"
