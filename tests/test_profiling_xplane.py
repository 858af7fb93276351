"""``utils/profiling.summarize_trace`` on a recorded capture: a very small
profile taken on the TPU v5e (``tests/golden/tiny_tpu.xplane.pb``: three calls
of one jitted ``tanh(x @ x).sum()`` while ``utils/tracing`` was on), read with
``jax.profiler.ProfileData`` as on the machine that has the chip: no ``xprof``
package. The times in it are the recording's, not this host's.
"""

import json
import pathlib
import shutil

import pytest

pytest.importorskip("jax")

from copycat_tpu.utils.profiling import (  # noqa: E402
    find_xplane_files, summarize_trace)

RECORDED = pathlib.Path(__file__).parent / "golden" / "tiny_tpu.xplane.pb"


@pytest.fixture
def trace_dir(tmp_path):
    session = tmp_path / "plugins" / "profile" / "2026_09_27_19_30_00"
    session.mkdir(parents=True)
    shutil.copy(RECORDED, session / "host.xplane.pb")
    return str(tmp_path)


def test_summarize_trace_reads_a_recorded_xplane(trace_dir):
    assert find_xplane_files(trace_dir)[0].endswith("host.xplane.pb")
    rows = summarize_trace(trace_dir, top=50)
    assert rows, "no device operations found in the recorded capture"
    names = [name for name, _, _ in rows]
    assert all("%" not in n and " = " not in n for n in names), names
    assert len(set(names)) == len(names)
    totals = [ms for _, ms, _ in rows]
    assert totals == sorted(totals, reverse=True) and totals[-1] > 0
    # three calls of one program: every operation ran a multiple of 3 times
    assert all(count % 3 == 0 for _, _, count in rows), rows
    assert summarize_trace(trace_dir, top=1) == rows[:1]


def test_the_recorded_profile_carries_the_tracers_annotations():
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(RECORDED))
    planes = {plane.name: plane for plane in data.planes}
    assert any(name.startswith("/device:TPU:") for name in planes)
    waits = [e for line in planes["/host:CPU"].lines for e in line.events
             if e.name == "engine.wait"]
    assert len(waits) == 3 and all(e.duration_ns > 0 for e in waits)
    modules = [e for name, plane in planes.items()
               if name.startswith("/device:TPU:") for line in plane.lines
               if line.name == "XLA Modules" for e in line.events]
    # the program's stage and the device's programs in one xplane: three
    # of each, in step. The device's own clock is not the host's: in this
    # recording each program starts 1.1 ms BEFORE the annotation of the
    # call that launched it, so the two lines agree to a millisecond or so
    assert len(modules) == 3
    for wait, module in zip(sorted(waits, key=lambda e: e.start_ns),
                            sorted(modules, key=lambda e: e.start_ns)):
        assert abs(module.start_ns - wait.start_ns) < 2e6
        assert module.duration_ns < wait.duration_ns


def test_cli_profile_device_prints_the_recorded_operations(trace_dir, capsys):
    from copycat_tpu import cli

    ns = type("A", (), dict(addresses=[], last=None, top=3, json=True,
                            diff=None, device=trace_dir))()
    assert cli._profile(ns) == 0
    rows = json.loads(capsys.readouterr().out)
    assert len(rows) == 3 and set(rows[0]) == {"op", "total_ms", "count"}
    assert rows[0]["op"] == "fusion" and rows[0]["count"] == 3
    assert rows[0]["total_ms"] > rows[1]["total_ms"] >= 0
