"""Process-environment helpers: the backend rule, the compile cache, and
the CPU-forced child environment.

Every entry point that runs device code (``python -m fmda_tpu``,
``benchmark/run.py``, ``chip_smoke.py``, ``__graft_entry__``) decides its
platform with :func:`select_backend` and places its persistent compile
cache with :func:`enable_compile_cache`; children that must stay off the
accelerator (virtual-mesh runs, fleet workers) get
:func:`cpu_forced_env`.  A chip belongs to one process: none of these
start a child that initialises a backend before the parent does.
"""

from __future__ import annotations

import os
from typing import Dict, Optional

#: The checkout that holds this package (it is run in place, not
#: pip-installed); the default compile cache lives under it.
_CHECKOUT_DIR = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NO_TPU_MESSAGE = (
    "no TPU found and no platform pinned: this command runs on a TPU by "
    "default and never falls back to the host on its own. Pass "
    "--platform cpu (or set JAX_PLATFORMS=cpu) to run on the CPU."
)


def cpu_forced_env(
    n_devices: Optional[int] = None, repo_dir: Optional[str] = None
) -> Dict[str, str]:
    """A child environment in which jax can only ever see the host CPU.

    ``n_devices`` sets ``--xla_force_host_platform_device_count`` (replacing
    any existing value) for virtual-mesh runs.  ``repo_dir`` is prepended to
    ``PYTHONPATH`` so the child imports this checkout.
    """
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    if n_devices is not None:
        flags = [
            f
            for f in env.get("XLA_FLAGS", "").split()
            if not f.startswith("--xla_force_host_platform_device_count")
        ]
        flags.append(f"--xla_force_host_platform_device_count={n_devices}")
        env["XLA_FLAGS"] = " ".join(flags)
    if repo_dir is not None:
        env["PYTHONPATH"] = repo_dir + os.pathsep + env.get("PYTHONPATH", "")
    return env


def select_backend(force_cpu: bool = False) -> None:
    """The one backend rule.

    ``force_cpu`` (``--platform cpu``) pins the host platform.  Otherwise
    a platform already pinned from outside — ``JAX_PLATFORMS``, or a
    ``jax.config.update("jax_platforms", ...)`` by a test harness or an
    embedding application — is respected as it is.  Otherwise the process
    requires a TPU: finding none it exits non-zero with
    :data:`NO_TPU_MESSAGE`; it never continues on the CPU by its own
    decision.  The backend is initialised in this process and nowhere
    else, so the chip is taken exactly once.
    """
    import jax

    if force_cpu:
        jax.config.update("jax_platforms", "cpu")
    elif not jax.config.jax_platforms and jax.default_backend() != "tpu":
        raise SystemExit(NO_TPU_MESSAGE)
    enable_compile_cache()


def enable_compile_cache() -> Optional[str]:
    """Place jax's persistent compilation cache; returns its directory
    (``None`` where the cache stays off).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax already reads its
    cache directory from it and nothing is set here.  Otherwise the cache
    goes to ``<checkout>/.jax_cache`` (git-ignored) — a fixed path,
    because the path is part of the cache key and a directory that moves
    never hits.

    The minimum-compile-time threshold drops from jax's 1 s to 0: the
    serving pool's bucket programs compile in 0.2-1.3 s each, so the
    default would leave most of a cold ``serve-fleet`` start uncached.

    Where the platform is pinned to the CPU the cache stays off.  XLA:CPU
    executables are cached as AOT results, and jaxlib 0.9.0's loader logs
    a multi-KB "machine features do not match ... could lead to SIGILL"
    error on every single hit, on the very machine that wrote the entry
    (its pseudo-features ``prefer-no-gather``/``prefer-no-scatter`` never
    match).  (The SIGABRT the previous jaxlib raised on such a load
    inside a donated train step is gone on 0.9.0 — re-tested with the
    trainer tests run twice against one cache.)  CPU runs are tests and
    dry runs, compile in seconds, and should not write into the
    checkout.  Read from the pin, not from the live backend, so calling
    this never initialises one.
    """
    import jax

    if (jax.config.jax_platforms or "").split(",")[0] == "cpu":
        return None
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir",
            os.path.join(_CHECKOUT_DIR, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir


def device_report() -> Dict[str, object]:
    """What jax runs on, as every report states it: the platform, the
    device kind and the device count — a ``backend`` field alone cannot
    tell a v5e from another TPU, or one chip from four."""
    import jax

    devices = jax.devices()
    return {
        "backend": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "n_devices": len(devices),
    }
