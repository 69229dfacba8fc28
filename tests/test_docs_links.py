"""Docs stay honest: every doc file / section / flag / path referenced
from docstrings, README.md and docs/*.md must exist (tools/check_docs_links.py)."""
import importlib.util
import os

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _checker():
    spec = importlib.util.spec_from_file_location(
        "check_docs_links", os.path.join(ROOT, "tools",
                                         "check_docs_links.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_docs_links_resolve(capsys):
    rc = _checker().main()
    out = capsys.readouterr().out
    assert rc == 0, f"broken doc references:\n{out}"


@pytest.mark.parametrize("path,generated", [
    ("benchmarks/out/", True), ("benchmarks/out/fig.json", True),
    (".jax_cache/", True), ("experiments/run.json", True),
    ("benchmarks/missing/", False), ("src/repro/nope.py", False)])
def test_gitignored_output_dirs_count_as_generated(path, generated):
    """A clean checkout lacks generated output directories; the checker
    accepts paths under the ones .gitignore lists, and only those."""
    assert _checker()._generated(path) is generated
