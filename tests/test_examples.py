"""The terminating examples run end-to-end in fresh interpreters.

Examples are the reference's user surface (`SURVEY.md` L6); running them
as subprocesses (like a user: fresh interpreter, fresh registry)
catches drift between the examples/docs and the library — the same
class-registration failure mode `test_standalone_server.py` guards on
the server side. All self-terminating examples run here; the serve-forever mains
(leader_election, atomic_value, group_membership, standalone_server) are
covered by the resource tests they demonstrate.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CASES = [
    # (example, argv, expected stdout fragment); the sizes are
    # tests/engines.py's device plane (8 groups, drives of 8 a group), so
    # that session_client.py runs programs the suite has compiled
    ("custom_resource.py", [], "stock after release: 10"),
    ("bulk_counters.py", ["8", "8"], "linearizable reads/sec"),
    ("device_batch.py", [], "done"),
    ("session_client.py", ["8", "8"], "lock handed over to backup"),
]


@pytest.mark.parametrize("example,argv,expect",
                         CASES, ids=[c[0] for c in CASES])
def test_example_runs_clean(example, argv, expect):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", example), *argv],
        capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    assert expect in out.stdout, out.stdout[-2000:]
