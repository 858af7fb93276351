"""The platform helpers: one in-process device question, and a compile
cache whose place is decided from outside.

``require_platform`` asks ``jax.devices()`` once and exits 2 unless the
platform is the one asked for; ``enable_compilation_cache`` leaves the
directory to ``JAX_COMPILATION_CACHE_DIR`` where that is set and uses one
fixed in-checkout path where it is not.
"""

import os

import pytest

from copycat_tpu.utils import platform

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_require_platform_names_the_device(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    info = platform.require_platform()
    assert info["platform"] == "cpu"
    assert info["device_kind"] and info["device_count"] >= 1
    assert info == platform.device_info()


@pytest.mark.parametrize("env", ["tpu", None])
def test_require_platform_exits_2_on_another_platform(monkeypatch, env):
    # asked for the TPU (by name, or by default with nothing set) while
    # this process runs on the CPU: a measurement path must fail, never
    # carry on under the wrong label
    if env is None:
        monkeypatch.delenv("JAX_PLATFORMS")
    else:
        monkeypatch.setenv("JAX_PLATFORMS", env)
    with pytest.raises(SystemExit) as exc:
        platform.require_platform()
    assert exc.value.code == 2


class TestCompilationCache:
    """Placement rules of ``enable_compilation_cache``.

    Config state is saved/restored because the suite's conftest already
    enabled the cache for this process.
    """

    @pytest.fixture(autouse=True)
    def _restore_config(self, monkeypatch):
        import jax

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        saved = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", saved)

    def test_env_set_code_sets_no_directory(self, monkeypatch):
        import jax

        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/tmp/fleet-cache")
        assert platform.enable_compilation_cache() == "/tmp/fleet-cache"
        # JAX's own handling of the variable stands: nothing written here
        assert jax.config.jax_compilation_cache_dir is None
        assert not os.path.exists("/tmp/fleet-cache")

    def test_env_unset_uses_the_fixed_in_checkout_path(self):
        import jax

        jax.config.update("jax_compilation_cache_dir", None)
        got = platform.enable_compilation_cache()
        assert got == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        assert os.path.isdir(got)

    def test_same_path_on_two_calls_whatever_home_and_tmpdir(
            self, monkeypatch, tmp_path):
        first = platform.enable_compilation_cache()
        monkeypatch.setenv("HOME", str(tmp_path / "home"))
        monkeypatch.setenv("TMPDIR", str(tmp_path / "tmp"))
        assert platform.enable_compilation_cache() == first
        assert not (tmp_path / "home").exists()

    def test_default_path_is_git_ignored(self):
        ignored = open(os.path.join(REPO, ".gitignore")).read().split()
        assert ".jax_cache/" in ignored

    def test_small_compiles_are_cached_too(self):
        import jax

        platform.enable_compilation_cache()
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.5

    def test_trim_only_touches_cache_entries(self, tmp_path):
        h = "ab" * 32
        for i in range(6):
            p = tmp_path / f"jit_f{i}-{h}-cache"
            p.write_bytes(b"x" * 100)
            os.utime(p, (i, i))
        precious = tmp_path / "precious.txt"
        precious.write_bytes(b"y" * 1000)   # over budget, but NOT ours
        platform._trim_cache_dir(str(tmp_path), max_bytes=350)
        left = sorted(q.name for q in tmp_path.iterdir())
        # least-recently-used cache entries dropped; user file untouched
        assert left == [f"jit_f3-{h}-cache", f"jit_f4-{h}-cache",
                        f"jit_f5-{h}-cache", "precious.txt"], left


def test_a_test_engine_takes_a_shared_shape_or_says_why():
    """Tier-1 runs from an empty compile cache on the driver, where every
    engine shape of its own costs seconds (``tests/engines.py``). Outside
    ``tests/benchmark``, a test builds its engine through that module, or
    constructs ``RaftGroups`` under a ``# shape:`` comment (on the call's
    first line or the line above) that says why the shape is its subject."""
    import ast
    import glob

    tests = os.path.join(REPO, "tests")
    engines = os.path.join(tests, "engines.py")
    with open(engines) as f:
        shared = [n.name for n in ast.parse(f.read()).body
                  if isinstance(n, ast.FunctionDef)]
    assert "device_plane" in shared
    unexplained = []
    for path in sorted(glob.glob(os.path.join(tests, "*.py"))):
        if path == engines:
            continue
        with open(path) as f:
            source = f.read()
        lines = source.splitlines()
        for node in ast.walk(ast.parse(source)):
            if not (isinstance(node, ast.Call)
                    and getattr(node.func, "id",
                                getattr(node.func, "attr", "")) == "RaftGroups"
                    and any(isinstance(a, ast.Constant) for a in
                            [*node.args, *(k.value for k in node.keywords
                                           if k.arg != "seed")])):
                continue
            near = lines[max(0, node.lineno - 2):node.lineno]
            if not any("# shape:" in line for line in near):
                unexplained.append(
                    f"{os.path.relpath(path, REPO)}:{node.lineno}")
    assert not unexplained, (
        "RaftGroups(<literal sizes>) with no '# shape:' reason; take a "
        f"shape of tests/engines.py ({', '.join(shared)}): {unexplained}")


def _project_scripts():
    """The ``name = "module:callable"`` lines of pyproject.toml: only
    ``[project.scripts]`` has them (read by hand: ``tomllib`` is not in
    every Python the project allows)."""
    import re

    with open(os.path.join(REPO, "pyproject.toml")) as f:
        return re.findall(r'^([\w-]+) = "([\w.]+:\w+)"$', f.read(), re.M)


@pytest.mark.parametrize("name,target", _project_scripts())
def test_a_console_script_points_at_a_callable(name, target):
    # what a deleted module's forgotten entry breaks: the script installs
    # and dies on its first line
    import importlib

    module, _, attr = target.partition(":")
    assert callable(getattr(importlib.import_module(module), attr)), target
