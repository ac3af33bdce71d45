"""The tree is what its documents say it is.

Every path a document names exists, the scripts at the root are the
documented entry points, no record of a run is tracked, and every tool
under ``tools/`` still starts. A deleted module that a document, the
README's entry points or a tool still names fails here.
"""
import fnmatch
import functools
import glob
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "docs", "*.md")))
TOOLS = sorted(os.path.basename(p)
               for p in glob.glob(os.path.join(REPO, "tools", "*.py")))
_TREES = ("paddle_tpu/", "tools/", "tests/", "benchmark/", "docs/",
          "examples/", "csrc/")


def _read(rel):
    with open(os.path.join(REPO, rel)) as f:
        return f.read()


def _git_ls_files(*flags):
    out = subprocess.run(["git", "ls-files", *flags], cwd=REPO, text=True,
                         capture_output=True, timeout=60)
    if out.returncode != 0:
        pytest.skip(f"not a git checkout: {out.stderr.strip()}")
    return out.stdout.split()


def _named_paths(text):
    """Back-quoted words that are a path under one of the repo's trees,
    or a bare ``*.py``; a ``::test`` or ``:line`` suffix dropped."""
    for quoted in re.findall(r"`([^`\n]+)`", text):
        word = re.split(r"::|:\d", quoted.split()[0])[0].rstrip(".,;)")
        if any(c in word for c in "<>*{}$"):
            continue
        if word.startswith(_TREES) or re.fullmatch(r"\w+\.py", word):
            yield word


@functools.lru_cache(maxsize=None)
def _module_names():
    """Base names of the Python files under the repo's trees: a document
    may name a module by its file name alone (``planner.py``)."""
    return frozenset(f for tree in _TREES
            for _, _, files in os.walk(os.path.join(REPO, tree))
            for f in files if f.endswith(".py"))


@pytest.mark.parametrize("doc", DOCS)
def test_every_path_a_document_names_exists(doc):
    missing = sorted({p for p in _named_paths(_read(doc))
                      if p not in _module_names()
                      and not os.path.exists(os.path.join(REPO, p))})
    assert not missing, f"{doc} names paths that do not exist: {missing}"


def test_root_scripts_are_the_documented_entry_points():
    readme = _read("README.md")
    scripts = sorted(os.path.basename(p)
                     for p in glob.glob(os.path.join(REPO, "*.py")))
    assert scripts
    undocumented = [p for p in scripts if f"`{p}`" not in readme
                    and f"python {p}`" not in readme]
    assert not undocumented, f"README.md does not name {undocumented}"
    entry_points = readme.split("## Driver entry points", 1)[1]
    named = set(re.findall(r"\*\*`(?:python3? )?([\w/]+\.py)", entry_points))
    assert named >= {"chip_smoke.py", "benchmark/run.py"}
    gone = sorted(p for p in named
                  if not os.path.exists(os.path.join(REPO, p)))
    assert not gone, f"README.md's entry points do not exist: {gone}"


def test_no_tracked_file_is_a_run_record():
    ignored = _git_ls_files("-ci", "--exclude-standard")
    assert not ignored, f"tracked although .gitignore names it: {ignored}"
    records = [p for p in _git_ls_files()
               if fnmatch.fnmatch(os.path.basename(p), "FLEET_r*.json")
               or fnmatch.fnmatch(os.path.basename(p), "BENCH_*.json")
               or p.startswith("runs/")]
    assert not records, f"tracked although it is a run's output: {records}"


@pytest.mark.parametrize("tool", TOOLS)
def test_every_tool_starts(tool):
    """The argparse tools answer ``--help``; ``kernel_verdicts.py`` takes
    no arguments and leaves with 2 and one line where there is no TPU."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    args = [] if tool == "kernel_verdicts.py" else ["--help"]
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", tool)] + args,
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    rc, says = (2, "no TPU") if tool == "kernel_verdicts.py" else (0, "usage")
    assert proc.returncode == rc, proc.stderr[-2000:]
    assert says in proc.stdout + proc.stderr
