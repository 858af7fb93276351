"""``chip_smoke.py`` rehearsed on the CPU: the script refuses to pass
without a TPU, and its phases run end to end at a tiny size (kernels in
interpret mode) so a wrong path, argument or comparison is found here and
not on the chip.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

jax = pytest.importorskip("jax")

import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def compiles():
    return chip_smoke.Compiles()


@pytest.mark.parametrize("args", [[], ["--chips", "4"]],
                         ids=["one-chip", "four-chips"])
def test_exits_nonzero_and_prints_no_ok_without_a_tpu(args):
    out = subprocess.run(
        [sys.executable, "chip_smoke.py", *args], cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "needs" in out.stderr and "TPU" in out.stderr


def test_raw_plane_tiny(compiles, capsys):
    # Also the regression test of the follower-side flow control in
    # ops/consensus.step: without it about a fifth of these groups'
    # replicas diverge at L=32, S=16 once an election delays a commit
    # (found by this comparison on the first chip run), for every seed.
    chip_smoke.raw_plane(compiles, seed=3, G=128, sample=128,
                         pallas_interpret=True)
    out = capsys.readouterr().out
    assert "equal the plain model" in out and "agree across replicas" in out


def test_bulk_plane_tiny(compiles, capsys):
    chip_smoke.bulk_plane(compiles, seed=3, G=16, per_group=32,
                          pallas_interpret=True)
    assert "equal the running sums" in capsys.readouterr().out


def test_served_path_tiny(compiles, capsys):
    chip_smoke.served_path(compiles, seed=3, counters=24, others=2, waves=2)
    assert "30 instances on the device" in capsys.readouterr().out


@pytest.mark.slow  # two mixed engines' programs compile for ~80 s here
def test_mesh_plane_tiny_on_four_virtual_devices(compiles, capsys):
    chip_smoke.mesh_plane(compiles, 3, jax.devices()[:4], G=16,
                          per_group=32, sample=4, pallas_interpret=True)
    out = capsys.readouterr().out
    assert "results identical to one chip" in out
    assert "zero collectives in the step" in out


def test_plain_model_catches_a_wrong_result():
    """The comparison has teeth: one flipped result fails the replay."""
    import numpy as np

    S = 16
    pattern = chip_smoke.mixed_pattern(S)
    valid = np.ones((1, 1, S), bool)
    tag = np.arange(1, S + 1).reshape(1, 1, S)
    index = np.arange(1, S + 1).reshape(1, 1, S)
    model = chip_smoke.PlainGroup()
    result = np.asarray([model.apply(int(pattern[0][j]), int(pattern[1][j]),
                                     int(pattern[2][j]), j + 1)
                         for j in range(S)]).reshape(1, 1, S)
    assert chip_smoke.replay_reports(
        (valid, tag, result, index), pattern, S, [0]) == S
    result[0, 0, 5] += 1
    with pytest.raises(AssertionError, match="plain model"):
        chip_smoke.replay_reports((valid, tag, result, index), pattern, S,
                                  [0])
