"""Docs stay truthful: every code path named in the project docs must exist.

Scans README.md, docs/*.md, EXPERIMENTS.md, and ROADMAP.md for repo-path
references (backtick-quoted paths and markdown link targets) and asserts
each resolves in the tree; ``path::symbol`` references additionally assert
the symbol occurs in the file. This is the tier-1 guard behind the CI docs
job — rename a module and the doc that points at it fails here, not in a
reader's head.
"""
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
DOCS = sorted(
    p for p in [ROOT / "README.md", ROOT / "EXPERIMENTS.md",
                ROOT / "ROADMAP.md", *(ROOT / "docs").glob("*.md")]
    if p.exists())

# a repo path reference: known top-level prefix, or any *.py/*.md/*.json
# relative path with a directory component
_PREFIXES = ("src/", "tests/", "benchmarks/", "examples/", "docs/")
_PATH_RE = re.compile(
    r"(?:[A-Za-z0-9_.-]+/)*[A-Za-z0-9_.-]+\.(?:py|md|json)")


def _doc_refs(text):
    # drop fenced code blocks first: they contain commands/diagrams, and a
    # stray ``` would otherwise invert the single-backtick pairing below
    text = re.sub(r"```.*?```", "", text, flags=re.DOTALL)
    for token in re.findall(r"`([^`\n]+)`", text):
        path, _, symbol = token.partition("::")
        if _PATH_RE.fullmatch(path) and ("/" in path
                                         or path.startswith(_PREFIXES)):
            yield path, symbol
    for target in re.findall(r"\]\(([^)#]+)\)", text):
        if not target.startswith(("http://", "https://", "mailto:")):
            yield target, ""


def _cases():
    for doc in DOCS:
        for path, symbol in _doc_refs(doc.read_text()):
            yield pytest.param(doc, path, symbol,
                               id=f"{doc.name}:{path}"
                                  + (f"::{symbol}" if symbol else ""))


@pytest.mark.parametrize("doc, path, symbol", _cases())
def test_doc_reference_resolves(doc, path, symbol):
    # repo-root paths, plus package-relative spellings like `kernels/ops.py`
    # (docs refer to modules the way imports do)
    candidates = [ROOT / path, ROOT / "src" / path, ROOT / "src/repro" / path]
    target = next((c for c in candidates if c.exists()), None)
    assert target is not None, (
        f"{doc.relative_to(ROOT)} references {path!r}, which does not exist")
    if symbol:
        # a trailing `*` names a family of symbols by their common prefix
        name = symbol.lstrip("_").split("(")[0].rstrip("*")
        assert name in target.read_text(), (
            f"{doc.relative_to(ROOT)} references {path}::{symbol}, "
            f"but the symbol does not occur in the file")


def test_docs_exist_and_nonempty():
    for required in ("README.md", "docs/architecture.md", "EXPERIMENTS.md"):
        p = ROOT / required
        assert p.exists() and p.stat().st_size > 500, required


def test_scanner_sees_references():
    """The scanner must actually find refs (guards against regex rot)."""
    readme_refs = list(_doc_refs((ROOT / "README.md").read_text()))
    arch_refs = list(_doc_refs((ROOT / "docs/architecture.md").read_text()))
    assert len(readme_refs) >= 5, readme_refs
    assert len(arch_refs) >= 10, arch_refs


def test_kernel_contracts_report_in_sync(analysis_results):
    """The committed per-kernel contract report is the analyzer's current
    output, byte for byte — change a kernel's grid, blocks, or probe set
    and this fails until the report is regenerated."""
    committed = ROOT / "docs" / "kernel_contracts.md"
    assert committed.exists(), "docs/kernel_contracts.md is missing"
    assert committed.read_text() == analysis_results["contracts"], (
        "docs/kernel_contracts.md is stale: regenerate with "
        "`PYTHONPATH=src python -m repro.analysis --write-contracts`")
