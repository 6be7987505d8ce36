"""The documents name only what the tree holds.

A backticked word in README.md, docs/*.md or the verify skill that
looks like a path of this repo (its first component is a top-level
entry git would commit) must exist; a ``:line`` or ``::name`` suffix is
stripped, ``<placeholder>`` reads as ``*``, and a glob must match
something. What a .gitignore lists (build outputs, run directories) is
no path of the repo and is not checked. There is no allowlist: a
document that names a path of the reference tree under a top-level name
this repo shares writes it as the reference's (``/root/reference/...``).

CHANGES.md, ROADMAP.md and PERF.md are history and are not linted.
"""
import fnmatch
import functools
import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = (["README.md"]
        + sorted("docs/" + f for f in os.listdir(os.path.join(ROOT, "docs"))
                 if f.endswith(".md"))
        + [".claude/skills/verify/SKILL.md"])

_WORD = re.compile(r"^[A-Za-z0-9_.\-/*<>\[\]]+$")
_SUFFIX = re.compile(r"(::[A-Za-z0-9_.:\[\]\-]+|:\d+(-\d+)?(,\d+(-\d+)?)*)$")


@functools.lru_cache(maxsize=None)
def _ignore_patterns(directory):
    listing = os.path.join(ROOT, directory, ".gitignore")
    if not os.path.isfile(listing):
        return ()
    with open(listing) as f:
        lines = [line.strip().rstrip("/") for line in f]
    return tuple(p for p in lines if p and not p.startswith("#"))


def _ignored(parts):
    """True if git would not commit the path: some component matches a
    line of the .gitignore beside it."""
    return any(fnmatch.fnmatch(name, pat)
               for i, name in enumerate(parts)
               for pat in _ignore_patterns(os.path.join(*parts[:i], "")))


@functools.lru_cache(maxsize=None)
def _top_level():
    return frozenset(e for e in os.listdir(ROOT)
                     if e != ".git" and not _ignored((e,)))


def _repo_paths(text, top):
    """(word as written, path to look for) for every backticked word
    whose first component is a top-level entry of the repo."""
    for span in re.findall(r"`([^`\n]+)`", text):
        for word in span.split():
            word = word.strip("(),;\"'")
            path = _SUFFIX.sub("", word).rstrip(".:")
            if not _WORD.match(path):
                continue
            parts = [p for p in path.split("/") if p]
            if not parts or path.startswith("/") or parts[0] not in top \
                    or _ignored(parts):
                continue
            yield word, re.sub(r"<[^<>/]*>", "*", path)


@pytest.mark.parametrize("doc", DOCS)
def test_paths_named_in_document_exist(doc):
    with open(os.path.join(ROOT, doc)) as f:
        text = f.read()
    missing = sorted({word for word, path in _repo_paths(text, _top_level())
                      if not glob.glob(os.path.join(ROOT, path))})
    assert not missing, "%s names paths the tree does not hold: %s" % (
        doc, missing)


def test_documented_knobs_are_read():
    """Every MXNET_* name docs/env_vars.md documents is read somewhere
    under mxnet_tpu/ or tools/ beyond its own define() (the converse of
    test_config.py::test_declared_knobs_documented)."""
    with open(os.path.join(ROOT, "docs", "env_vars.md")) as f:
        names = set(re.findall(r"\bMXNET_[A-Z0-9_]+\b", f.read()))
    assert names
    read = set()
    for top in ("mxnet_tpu", "tools"):
        for dirpath, _, files in os.walk(os.path.join(ROOT, top)):
            for name in files:
                if not name.endswith((".py", ".sh", ".cc", ".h")):
                    continue
                with open(os.path.join(dirpath, name), errors="ignore") as f:
                    src = f.read()
                if dirpath.endswith("mxnet_tpu") and name == "config.py":
                    src = re.sub(r"define\(\s*\"MXNET_[A-Z0-9_]+\"", "", src)
                read.update(re.findall(r"\bMXNET_[A-Z0-9_]+\b", src))
    unread = sorted(names - read)
    assert not unread, "documented but read nowhere: %s" % unread


@pytest.fixture
def require_cpu_fleet(monkeypatch):
    """tools/chaos_fleet.py's check, loaded without leaving the
    module's import-time marks (a precision default, a path entry) on
    the tests that follow."""
    import importlib.util
    import sys
    monkeypatch.setenv("MXNET_MATMUL_PRECISION",
                       os.environ.get("MXNET_MATMUL_PRECISION", "highest"))
    monkeypatch.setattr(sys, "path", list(sys.path))
    spec = importlib.util.spec_from_file_location(
        "chaos_fleet", os.path.join(ROOT, "tools", "chaos_fleet.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.require_cpu_fleet


@pytest.mark.parametrize("platforms", [None, "tpu"])
def test_subprocess_fleet_refuses_without_cpu_pin(
        monkeypatch, require_cpu_fleet, platforms):
    """Replica processes cannot share a chip: the fleet refuses unless
    the environment pins the CPU."""
    if platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
    with pytest.raises(SystemExit, match="cannot share a chip"):
        require_cpu_fleet()


def test_subprocess_fleet_passes_with_cpu_pin(monkeypatch,
                                              require_cpu_fleet):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert require_cpu_fleet() is None
