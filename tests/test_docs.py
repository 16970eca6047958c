"""Docs surface (ISSUE 3 satellites): README/docs exist, internal links
resolve, and the link checker itself works — the same check the CI docs
job runs, kept in tier-1 so a broken link fails locally first."""

import os
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "scripts"))

import check_docs_links  # noqa: E402


def test_readme_and_architecture_doc_exist():
    readme = (REPO / "README.md").read_text()
    arch = (REPO / "docs" / "architecture.md").read_text()
    # README covers the quickstart + package map the issue asks for
    for needle in ("pytest", "bench/run.py", "core", "volume", "serving",
                   "benchmarks", "docs/architecture.md"):
        assert needle in readme, needle
    # architecture doc documents the plan->execution contract + recipe
    for needle in ("CompiledPlan", "compile_plan", "states", "Adding a primitive",
                   "CONV_PRIMS", "overlap_save"):
        assert needle in arch, needle


def test_no_broken_relative_links():
    problems = check_docs_links.broken_links(REPO)
    assert problems == []


def test_link_checker_catches_breakage(tmp_path):
    (tmp_path / "docs").mkdir()
    (tmp_path / "README.md").write_text(
        "[ok](docs/a.md) [bad](docs/missing.md) [ext](https://x.invalid/y) "
        "[anchor](#sec) [img skipped] ![alt](missing.png)"
    )
    (tmp_path / "docs" / "a.md").write_text("[up](../README.md)")
    problems = check_docs_links.broken_links(tmp_path)
    assert problems == ["README.md: broken link -> docs/missing.md"]


def test_module_docstrings_state_patch_invariants():
    """The satellite: tiler/executor docstrings carry the geometry
    contract new contributors need (core, FOV overlap, shifted edges)."""
    from repro.volume import executor, tiler

    for mod in (tiler, executor):
        doc = mod.__doc__ or ""
        for needle in ("core", "FOV", "shifted"):
            assert needle in doc, (mod.__name__, needle)


def test_example_commands_in_readme_are_runnable():
    """Quickstart commands reference real files."""
    readme = (REPO / "README.md").read_text()
    for path in ("bench/run.py", "benchmarks/table5_throughput.py",
                 "tests/_hypothesis_compat.py"):
        assert path in readme
        assert os.path.exists(REPO / path), path
