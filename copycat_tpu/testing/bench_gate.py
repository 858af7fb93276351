"""CI perf-regression gate over ``bench.py --metrics-json`` artifacts.

The bench trajectory finally has teeth: CI's ``bench-baseline`` job runs
the tiny spi + sharded smokes, then this gate compares each artifact's
headline value against the committed window in
``tests/golden/bench_baseline.json`` — per scenario, ``floor =
baseline x (1 - tolerance)`` (tolerance defaults to 0.25: CI-host
jitter, not a quality bar). Below the floor fails the job and prints
the exact update command; above ``baseline x (1 + tolerance)`` passes
with a "baseline looks stale" note so genuine wins get captured rather
than silently widening the window.

The golden records the value PLUS the artifact's ``meta`` block (git
SHA, knob overrides, host fingerprint — ``bench._artifact_meta``), so a
miss can be explained: a different host or knob set is a different
experiment, not a regression.

Usage (no jax import — artifacts are plain JSON)::

    python -m copycat_tpu.testing.bench_gate A.json B.json
    python -m copycat_tpu.testing.bench_gate A.json --update-golden
"""

from __future__ import annotations

import argparse
import json
import os
import sys

DEFAULT_TOLERANCE = 0.25
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_GOLDEN = os.path.join(_REPO_ROOT, "tests", "golden",
                              "bench_baseline.json")


def load_golden(path: str) -> dict:
    try:
        with open(path) as f:
            golden = json.load(f)
    except FileNotFoundError:
        golden = {}
    golden.setdefault("tolerance", DEFAULT_TOLERANCE)
    golden.setdefault("scenarios", {})
    return golden


def gate_artifact(artifact: dict, golden: dict) -> tuple[bool, str]:
    """Judge one artifact against the golden window; returns
    ``(passed, one-line verdict)``."""
    scenario = artifact.get("scenario", "?")
    value = artifact.get("value")
    unit = artifact.get("unit", "?")
    if not isinstance(value, (int, float)) or value <= 0:
        return False, (f"{scenario}: artifact carries no positive "
                       f"headline value ({value!r})")
    entry = golden["scenarios"].get(scenario)
    if entry is None:
        return False, (f"{scenario}: no committed baseline — record one "
                       f"with --update-golden")
    if entry.get("unit") != unit:
        return False, (f"{scenario}: unit changed "
                       f"({entry.get('unit')!r} -> {unit!r}) — the "
                       f"scenario is measuring something else; "
                       f"--update-golden after reviewing")
    tolerance = golden["tolerance"]
    baseline = float(entry["value"])
    floor = baseline * (1.0 - tolerance)
    if value < floor:
        verdict = (f"{scenario}: REGRESSION {value:,.1f} {unit} < "
                   f"floor {floor:,.1f} (baseline {baseline:,.1f} "
                   f"-{tolerance:.0%})")
        rec = (entry.get("recorded") or {}).get("host") or {}
        here = (artifact.get("meta") or {}).get("host") or {}
        probe = ("hostname", "machine", "cpus")
        if rec and here and any(rec.get(k) != here.get(k)
                                for k in probe):
            verdict += (f" — note: baseline was recorded on "
                        f"{rec.get('hostname')}/{rec.get('machine')}/"
                        f"{rec.get('cpus')}cpu, this run is "
                        f"{here.get('hostname')}/{here.get('machine')}/"
                        f"{here.get('cpus')}cpu; a different machine is "
                        f"a different experiment — refresh the baseline "
                        f"on THIS runner before reading this as a "
                        f"regression")
        return False, verdict
    if value > baseline * (1.0 + tolerance):
        return True, (f"{scenario}: ok {value:,.1f} {unit} — ABOVE the "
                      f"+{tolerance:.0%} window (baseline "
                      f"{baseline:,.1f} looks stale; consider "
                      f"--update-golden)")
    return True, (f"{scenario}: ok {value:,.1f} {unit} (baseline "
                  f"{baseline:,.1f}, floor {floor:,.1f})")


def update_golden(artifacts: list[dict], golden: dict) -> dict:
    for artifact in artifacts:
        # only value/unit/meta are recorded — bulky run-local payloads
        # ("metrics" snapshots, retained "series" windows) are tolerated
        # on the artifact but never committed into the golden
        entry = {
            "value": artifact["value"],
            "unit": artifact.get("unit"),
            "recorded": artifact.get("meta", {}),
        }
        golden["scenarios"][artifact["scenario"]] = entry
    return golden


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m copycat_tpu.testing.bench_gate",
        description="compare bench --metrics-json artifacts against the "
                    "committed bench_baseline.json window")
    parser.add_argument("artifacts", nargs="+", metavar="ARTIFACT.json")
    parser.add_argument("--golden", default=DEFAULT_GOLDEN,
                        help="baseline file (default: "
                             "tests/golden/bench_baseline.json)")
    parser.add_argument("--update-golden", action="store_true",
                        help="rewrite the baseline entries from these "
                             "artifacts instead of gating")
    args = parser.parse_args(argv)

    artifacts = []
    for path in args.artifacts:
        with open(path) as f:
            artifacts.append(json.load(f))
    golden = load_golden(args.golden)

    if args.update_golden:
        golden = update_golden(artifacts, golden)
        with open(args.golden, "w") as f:
            json.dump(golden, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"bench-gate: baseline updated for "
              f"{', '.join(a['scenario'] for a in artifacts)} "
              f"-> {args.golden}")
        return 0

    failed = False
    for artifact in artifacts:
        ok, line = gate_artifact(artifact, golden)
        print(f"bench-gate: {line}")
        if not ok:
            failed = True
    if failed:
        cmd = ("python -m copycat_tpu.testing.bench_gate "
               + " ".join(args.artifacts) + " --update-golden")
        print(f"bench-gate: FAILED — if the change is intentional and "
              f"reviewed, refresh the window with:\n  {cmd}")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
