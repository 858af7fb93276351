"""The documents name files and knobs that exist.

One case a document. In each, every back-ticked path that has a directory
in it and ends in a source or record suffix, and every bare ``UPPER_CASE.md``
name, resolves from the repository's root or from ``copycat_tpu/`` (the
documents write ``server/raft_group.py``); a ``:line`` or ``:a-b`` after it
is not past the file's end; and a back-ticked ``COPYCAT_*`` name is a
registered knob. Paths of the reference (``/root/reference/``), bare file
names (``apply.py``), globs and placeholders are left alone.

``PERF.md``, ``ROADMAP.md``, ``CHANGES.md``, ``ADVICE.md`` and
``benchmarks/README*.md`` hold history and are not held.
"""

import glob
import os
import re

import pytest

from copycat_tpu.utils import knobs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = ["README.md", "PARITY.md", "MIGRATION.md", "examples/README.md",
             *sorted(os.path.relpath(p, REPO) for p in
                     glob.glob(os.path.join(REPO, "docs", "*.md")))]

_SUFFIXES = r"(?:py|md|jsonl|json|yml|c|toml)"
_LINES = r"(?::(\d+)(?:-(\d+))?)?"
# `dir/.../name.suffix`, then an optional `:line`, `:a-b` or `::test_name`
_PATH = re.compile(rf"^([\w.\-]+(?:/[\w.\-]+)+\.{_SUFFIXES}){_LINES}(?:::.*)?$")
_BARE_MD = re.compile(rf"(?<![\w/.\-])([A-Z][A-Z0-9_]*\.md){_LINES}(?![\w/])")
_KNOB = re.compile(r"^(COPYCAT_[A-Z0-9_]+)(?:=.*)?$")


def _resolve(path):
    for base in (REPO, os.path.join(REPO, "copycat_tpu")):
        full = os.path.join(base, path)
        if os.path.isfile(full):
            return full
    return None


def _lines_of(full):
    with open(full, errors="replace") as f:
        return sum(1 for _ in f)


def _broken(text):
    """Every pointer of ``text`` that points at nothing, as strings."""
    wrong = []

    def held(path, first, last, shown):
        full = _resolve(path)
        if full is None:
            wrong.append(f"{shown}: no such file")
        elif first and int(last or first) > (n := _lines_of(full)):
            wrong.append(f"{shown}: past the file's {n} lines")

    for token in re.findall(r"`([^`\n]+)`", text):
        token = token.strip()
        if token.startswith(("/", "~", "http")):
            continue
        m = _PATH.match(token)
        if m:
            held(m.group(1), m.group(2), m.group(3), token)
            continue
        m = _KNOB.match(token)
        if m and m.group(1) not in knobs.REGISTRY:
            wrong.append(f"{token}: not a registered knob")
    for m in _BARE_MD.finditer(text):
        held(m.group(1), m.group(2), m.group(3), m.group(0))
    return sorted(set(wrong))


@pytest.mark.parametrize("document", DOCUMENTS)
def test_a_document_points_at_files_and_knobs_that_exist(document):
    with open(os.path.join(REPO, document)) as f:
        wrong = _broken(f.read())
    assert not wrong, f"{document} points at nothing with: {wrong}"


def test_the_rule_catches_what_it_is_for():
    text = ("see `copycat_tpu/no_such.py`, `server/raft_group.py:999999`, "
            "NO_SUCH_RECORD.md and `COPYCAT_NO_SUCH_KNOB`; but not "
            "`/root/reference/README.md:8`, `apply.py`, `docs/*.md`, "
            "`server/raft_group.py:1-3`, README.md or `COPYCAT_GROUPS`")
    assert _broken(text) == [
        "COPYCAT_NO_SUCH_KNOB: not a registered knob",
        "NO_SUCH_RECORD.md: no such file",
        "copycat_tpu/no_such.py: no such file",
        "server/raft_group.py:999999: past the file's "
        f"{_lines_of(_resolve('server/raft_group.py'))} lines"]
