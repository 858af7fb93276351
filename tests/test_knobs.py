"""utils/knobs.py registry tests (satellite of the copycheck PR).

Three sync properties, asserted — not hand-maintained:

1. README's *Knob reference* section is byte-identical to the
   registry's renderer (regenerate: ``python -m copycat_tpu.utils.knobs``);
2. every ``COPYCAT_*`` name the tree passes to ``knobs.get_*`` is
   registered, and every registered knob is actually read somewhere
   (no zombie registry rows);
3. the typed getters honor env overrides, call-site defaults for
   computed knobs, and the documented bool normalization.
"""

import ast
import os

import pytest

from copycat_tpu.analysis.engine import discover
from copycat_tpu.utils import knobs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _knob_literals_in_tree() -> set[str]:
    """Every COPYCAT_* name passed to a knobs getter anywhere."""
    used: set[str] = set()
    getters = set(knobs.__dict__) & {
        "get_raw", "get_str", "get_int", "get_float", "get_bool"}
    for rel in discover(REPO):
        tree = ast.parse(open(os.path.join(REPO, rel),
                              encoding="utf-8").read())
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in getters and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                used.add(node.args[0].value)
    return used


def test_readme_knob_table_in_sync():
    readme = open(os.path.join(REPO, "README.md"), encoding="utf-8").read()
    section = knobs.readme_section(readme)
    assert section is not None, "README lost the knobs:begin/end markers"
    assert section == knobs.render_markdown(), (
        "README Knob reference drifted from utils/knobs.py — regenerate "
        "with `python -m copycat_tpu.utils.knobs` and paste between the "
        "markers (or fix the registry)")


def test_every_used_knob_is_registered_and_vice_versa():
    used = _knob_literals_in_tree()
    registered = set(knobs.REGISTRY)
    assert used - registered == set(), (
        f"unregistered knobs in code: {sorted(used - registered)}")
    zombies = registered - used
    assert zombies == set(), (
        f"registered knobs no code reads: {sorted(zombies)}")


def test_registry_docs_complete():
    for knob in knobs.REGISTRY.values():
        assert knob.doc.strip(), f"{knob.name} has no doc"
        assert knob.kind in ("int", "float", "str", "bool", "raw"), knob
        assert knob.default_text(), knob.name
        if knob.default is None and knob.kind != "raw":
            # computed default: the call site must pass default=, and
            # the README needs a human-readable rule
            assert knob.default_doc, (
                f"{knob.name}: computed default needs default_doc")


def test_typed_getters(monkeypatch):
    monkeypatch.delenv("COPYCAT_REPL_WINDOW", raising=False)
    assert knobs.get_int("COPYCAT_REPL_WINDOW") == 64
    monkeypatch.setenv("COPYCAT_REPL_WINDOW", "7")
    assert knobs.get_int("COPYCAT_REPL_WINDOW") == 7

    monkeypatch.delenv("COPYCAT_REPL_MAX_INFLIGHT", raising=False)
    # computed default: registry has none, the call site provides it
    assert knobs.get_int("COPYCAT_REPL_MAX_INFLIGHT", default=512) == 512
    with pytest.raises(ValueError):
        knobs.get_int("COPYCAT_REPL_MAX_INFLIGHT")

    monkeypatch.setenv("COPYCAT_CLUSTER_NOPE", "1")
    with pytest.raises(KeyError):
        knobs.get_int("COPYCAT_CLUSTER_NOPE")


def test_bool_normalization(monkeypatch):
    monkeypatch.delenv("COPYCAT_SNAPSHOTS", raising=False)
    assert knobs.get_bool("COPYCAT_SNAPSHOTS") is True  # registered default
    for off in ("0", "false", "OFF", "no", ""):
        monkeypatch.setenv("COPYCAT_SNAPSHOTS", off)
        assert knobs.get_bool("COPYCAT_SNAPSHOTS") is False, off
    for on in ("1", "true", "yes", "on"):
        monkeypatch.setenv("COPYCAT_SNAPSHOTS", on)
        assert knobs.get_bool("COPYCAT_SNAPSHOTS") is True, on


def test_raw_tristate(monkeypatch):
    monkeypatch.delenv("COPYCAT_INVARIANTS", raising=False)
    assert knobs.get_raw("COPYCAT_INVARIANTS") is None
    monkeypatch.setenv("COPYCAT_INVARIANTS", "strict")
    assert knobs.get_raw("COPYCAT_INVARIANTS") == "strict"


def test_cli_renders_the_readme_body(capsys):
    knobs.main()
    out = capsys.readouterr().out
    assert out == knobs.render_markdown()
    assert "| `COPYCAT_SNAPSHOTS` | `1` |" in out
