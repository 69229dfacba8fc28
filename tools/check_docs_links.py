#!/usr/bin/env python
"""Docs link checker: keep README/ARCHITECTURE and docstring references
honest.

Checks, across the repo:

1. every ``*.md`` file referenced from a Python docstring/comment under
   ``src/``, ``tests/``, ``benchmarks/`` or ``examples/``, or in a
   script at the repo root (``chip_smoke.py``), exists
   (this is what used to rot: docstrings cited a ``DESIGN.md`` that was
   never committed);
2. every ``docs/ARCHITECTURE.md §N`` citation points at a section that
   actually exists in that file;
3. every relative markdown link ``[text](path)`` in ``README.md`` and
   ``docs/*.md`` resolves to a real file;
4. every ``--flag`` mentioned in README/docs appears somewhere in the
   Python sources (so CLI documentation tracks argparse reality);
5. every backticked path ending in ``.py``/``.md`` (or ``path/``)
   mentioned in README/docs exists, resolved against the repo root and
   ``src/`` — or lies in a directory ``.gitignore`` lists (generated
   output such as ``benchmarks/out/``, which a clean checkout lacks).

Run:  python tools/check_docs_links.py   (exit 1 on any broken ref)
"""
from __future__ import annotations

import os
import re
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
# tools/ is excluded: this checker's own docstring names rot patterns
PY_DIRS = ("src", "tests", "benchmarks", "examples")
DOC_FILES = ["README.md"] + [
    os.path.join("docs", f) for f in sorted(os.listdir(
        os.path.join(ROOT, "docs"))) if f.endswith(".md")
] if os.path.isdir(os.path.join(ROOT, "docs")) else ["README.md"]

MD_REF = re.compile(r"[A-Za-z0-9_\-./]*[A-Za-z0-9_\-]+\.md\b")
SECTION_REF = re.compile(r"ARCHITECTURE\.md\s+§(\d+)")
MD_LINK = re.compile(r"\[[^\]]*\]\(([^)#\s]+)(?:#[^)\s]*)?\)")
FLAG_REF = re.compile(r"(--[a-z][a-z0-9-]+)\b")
CODE_PATH = re.compile(r"`([A-Za-z0-9_\-./]+(?:\.py|\.md|/))`")


def _py_files():
    for f in sorted(os.listdir(ROOT)):
        if f.endswith(".py"):
            yield os.path.join(ROOT, f)
    for d in PY_DIRS:
        for dirpath, _, files in os.walk(os.path.join(ROOT, d)):
            if "__pycache__" in dirpath:
                continue
            for f in files:
                if f.endswith(".py"):
                    yield os.path.join(dirpath, f)


def _exists(rel: str) -> bool:
    rel = rel.strip("`'\"")
    return (os.path.exists(os.path.join(ROOT, rel))
            or os.path.exists(os.path.join(ROOT, "src", rel)))


def _ignored_dirs() -> tuple:
    """Plain directory entries of .gitignore (``name/``, no globs)."""
    path = os.path.join(ROOT, ".gitignore")
    if not os.path.exists(path):
        return ()
    with open(path) as f:
        return tuple(line.strip().lstrip("/") for line in f
                     if line.strip().endswith("/") and "*" not in line)


def _generated(rel: str) -> bool:
    return rel.strip("`'\"").startswith(_ignored_dirs())


def _arch_sections() -> set:
    path = os.path.join(ROOT, "docs", "ARCHITECTURE.md")
    if not os.path.exists(path):
        return set()
    with open(path) as f:
        return {int(m.group(1))
                for m in re.finditer(r"^## (\d+)\.", f.read(), re.M)}


def main() -> int:
    errors = []
    sections = _arch_sections()

    # 1 + 2: markdown + section references from Python sources
    for path in _py_files():
        rel = os.path.relpath(path, ROOT)
        with open(path) as f:
            text = f.read()
        for m in MD_REF.finditer(text):
            ref = m.group(0)
            if ref.startswith(("http", "www.")) or "*" in ref:
                continue
            if not _exists(ref) and not _exists(os.path.basename(ref)):
                errors.append(f"{rel}: references missing file {ref!r}")
        for m in SECTION_REF.finditer(text):
            if int(m.group(1)) not in sections:
                errors.append(f"{rel}: cites ARCHITECTURE.md §{m.group(1)}"
                              f" which does not exist (have {sorted(sections)})")

    # 3, 4, 5: doc-file links, flags, backticked paths
    py_corpus = "\n".join(open(p).read() for p in _py_files())
    for doc in DOC_FILES:
        doc_path = os.path.join(ROOT, doc)
        if not os.path.exists(doc_path):
            continue
        with open(doc_path) as f:
            text = f.read()
        for m in MD_LINK.finditer(text):
            target = m.group(1)
            if target.startswith(("http", "mailto:")):
                continue
            if not _exists(os.path.normpath(
                    os.path.join(os.path.dirname(doc), target))) \
                    and not _exists(target):
                errors.append(f"{doc}: broken link -> {target}")
        for m in FLAG_REF.finditer(text):
            flag = m.group(1)
            if flag not in py_corpus:
                errors.append(f"{doc}: documents flag {flag} not found in "
                              "any Python source")
        for m in CODE_PATH.finditer(text):
            if not _exists(m.group(1)) and not _generated(m.group(1)):
                errors.append(f"{doc}: mentions path `{m.group(1)}` which "
                              "does not exist (checked root and src/)")
        for m in SECTION_REF.finditer(text):
            if int(m.group(1)) not in sections:
                errors.append(f"{doc}: cites ARCHITECTURE.md §{m.group(1)}"
                              " which does not exist")

    if errors:
        print(f"docs link check: {len(errors)} problem(s)")
        for e in errors:
            print(f"  {e}")
        return 1
    print("docs link check: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
