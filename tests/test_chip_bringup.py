"""Contracts the first chip run put in place (PR 21), checkable on the CPU:
where the compile cache lives, and that multi-process modes refuse on a
chip host instead of hanging.  The chip itself is exercised by
``chip_smoke.py``."""

import os
import subprocess
import sys

import pytest

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------ compile cache
def test_compile_cache_outside_dir_is_left_alone(monkeypatch):
    from shifu_tpu import compile_cache
    monkeypatch.setenv(compile_cache.ENV, "/some/outside/dir")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.configure() == "/some/outside/dir"
    assert os.environ[compile_cache.ENV] == "/some/outside/dir"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_dir_in_checkout(monkeypatch):
    from shifu_tpu import compile_cache
    monkeypatch.delenv(compile_cache.ENV)
    # hide the imported jax: the suite's own cache config stays untouched
    monkeypatch.delitem(sys.modules, "jax")
    assert compile_cache.configure() == os.path.join(REPO, ".jax_cache")
    assert os.environ[compile_cache.ENV] == os.path.join(REPO, ".jax_cache")
    assert compile_cache.configure() == os.path.join(REPO, ".jax_cache")


def test_cli_does_not_import_jax_to_place_the_cache():
    """``lint`` (and a no-op ``initialize_distributed``) stay jax-free:
    the helper runs first thing in ``cli._dispatch`` without importing
    it, and the fixed default reaches the environment."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = REPO
    code = ("import os, sys; from shifu_tpu.cli import main; "
            "rc = main(['lint', '--list-rules']); "
            "print('JAX' if 'jax' in sys.modules else 'NOJAX', "
            "os.environ['JAX_COMPILATION_CACHE_DIR']); sys.exit(rc)")
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip().splitlines()[-1] == \
        "NOJAX " + os.path.join(REPO, ".jax_cache")


# ---------------------------------------------------- one process per chip
@pytest.fixture
def on_a_chip_host(monkeypatch):
    """A TPU default backend, and a Popen that must never be reached."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def no_spawn(*a, **k):
        raise AssertionError("spawned a child on a chip host")
    monkeypatch.setattr(subprocess, "Popen", no_spawn)


def test_fleet_and_multihost_refuse_on_chip(on_a_chip_host, tmp_path,
                                            capsys):
    from shifu_tpu.cli import main
    from shifu_tpu.config.errors import ErrorCode, ShifuError
    from shifu_tpu.serve.router import run_fleet
    with pytest.raises(ShifuError) as ei:
        run_fleet(str(tmp_path), replicas=2, port=0)
    assert ei.value.error_code is ErrorCode.ERROR_ONE_PROCESS_PER_CHIP
    # the CLI surface: coded message, exit 1, no traceback
    assert main(["--dir", str(tmp_path), "serve", "--replicas", "2",
                 "--port", "0"]) == 1
    assert "[1064]" in capsys.readouterr().err


def test_children_allowed_on_cpu_backend():
    from shifu_tpu.parallel.mesh import refuse_children_on_chip
    assert jax.default_backend() == "cpu"
    refuse_children_on_chip("anything")        # no raise

