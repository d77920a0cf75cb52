"""The one backend rule, the compile-cache placement and the CPU-forced
child environment (fmda_tpu.utils.env) — what every entry point uses to
decide where it runs, so a run can never look like a chip run when it
was not."""

import os
import subprocess
import sys

import jax
import pytest

from fmda_tpu.utils import env as env_mod
from fmda_tpu.utils.env import (
    NO_TPU_MESSAGE,
    cpu_forced_env,
    device_report,
    enable_compile_cache,
    select_backend,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_cpu_forced_env_forces_cpu_and_device_count(monkeypatch):
    env = cpu_forced_env(6, repo_dir="/some/repo")
    assert env["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=6" in env["XLA_FLAGS"]
    assert env["PYTHONPATH"].startswith("/some/repo" + os.pathsep)
    # replaces a prior device-count flag instead of stacking a second one
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=2 --xla_foo=1")
    env = cpu_forced_env(8)
    assert env["XLA_FLAGS"].count(
        "--xla_force_host_platform_device_count") == 1
    assert "--xla_force_host_platform_device_count=8" in env["XLA_FLAGS"]
    assert "--xla_foo=1" in env["XLA_FLAGS"]
    # nothing but the pin, the flag and the path differs from the parent
    changed = {k for k in env if os.environ.get(k) != env[k]}
    assert changed <= {"JAX_PLATFORMS", "XLA_FLAGS", "PYTHONPATH"}


def test_unpinned_without_a_tpu_exits_nonzero_naming_platform_cpu():
    """No pin, no TPU: the command must stop — non-zero, no report, and
    a message that names the way out — never carry on on the CPU."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-m", "fmda_tpu", "serve-fleet", "--sessions", "2",
         "--ticks", "1", "--hidden", "4"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "--platform cpu" in proc.stderr
    assert NO_TPU_MESSAGE in proc.stderr
    assert "ticks_served" not in proc.stdout


def test_pinned_platform_and_platform_cpu_are_respected():
    # the harness pins jax_platforms=cpu (conftest): respected, no exit
    assert jax.config.jax_platforms == "cpu"
    select_backend()
    select_backend(force_cpu=True)
    assert jax.config.jax_platforms == "cpu"
    assert device_report() == {
        "backend": "cpu", "device_kind": "cpu",
        "n_devices": len(jax.devices())}


def test_cli_platform_accepts_only_cpu():
    from fmda_tpu.cli import build_parser

    parser = build_parser()
    assert parser.parse_args(
        ["demo", "--platform", "cpu"]).platform == "cpu"
    assert parser.parse_args(["demo"]).platform is None
    for gone in ("auto", "ambient"):
        with pytest.raises(SystemExit):
            parser.parse_args(["demo", "--platform", gone])


@pytest.fixture
def unpinned_config():
    """jax's platform pin lifted for the duration (the backend is already
    initialised, so nothing re-initialises), every touched flag restored."""
    names = ("jax_platforms", "jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {n: getattr(jax.config, n) for n in names}
    jax.config.update("jax_platforms", None)
    try:
        yield
    finally:
        for name, value in saved.items():
            jax.config.update(name, value)


def test_compile_cache_goes_to_the_fixed_checkout_path(
        unpinned_config, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = enable_compile_cache()
    # fixed: no temporary name, pid or time in it — two processes (and
    # two chip calls) compute the identical directory
    assert path == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    # the serving buckets compile in well under jax's 1 s default
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_compile_cache_env_var_means_nothing_is_set_in_code(
        unpinned_config, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    enable_compile_cache()
    # jax reads the variable itself at start-up; the code must not
    # override (or re-set) the directory
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_stays_off_where_the_cpu_is_pinned(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert jax.config.jax_platforms == "cpu"
    assert enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir is None


def test_no_code_assigns_the_cache_dir_anywhere_else():
    """``jax.config.update("jax_compilation_cache_dir", ...)`` needs the
    quoted flag name; only utils/env.py may carry it."""
    carriers = set()
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("__pycache__", "chiprun_out")]
        for name in files:
            path = os.path.join(root, name)
            if name.endswith(".py") and not os.path.samefile(path, __file__):
                with open(path, encoding="utf-8") as fh:
                    if '"jax_compilation_cache_dir"' in fh.read():
                        carriers.add(os.path.relpath(path, REPO))
    assert carriers == {os.path.relpath(env_mod.__file__, REPO)}
