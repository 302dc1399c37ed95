"""The persistent compile cache is placed from outside the program
(`datafusion_tpu/__init__.py`): `JAX_COMPILATION_CACHE_DIR` wins
untouched; otherwise a fixed path inside the checkout, except under an
explicit CPU pin.  Each case is a fresh interpreter — the decision is
taken at import."""

from __future__ import annotations

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = (
    "import datafusion_tpu, jax; "
    "print(jax.config.jax_compilation_cache_dir); "
    "print(jax.config.jax_persistent_cache_min_compile_time_secs)"
)


def _import_with(**env_overrides):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR",
                        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS")}
    env.update(env_overrides)
    env["PYTHONPATH"] = REPO
    # importing the package initialises no backend, so the unpinned
    # cases never look for a chip
    proc = subprocess.run(
        [sys.executable, "-c", _PROBE], cwd="/", env=env,
        capture_output=True, text=True, timeout=120, check=True,
    )
    cache_dir, min_secs = proc.stdout.strip().splitlines()[-2:]
    return cache_dir, float(min_secs)


def test_env_var_is_left_alone():
    cache_dir, _ = _import_with(JAX_COMPILATION_CACHE_DIR="/x")
    assert cache_dir == "/x"
    assert not os.path.exists(os.path.join(REPO, ".jax_cache", "x"))


def test_unset_goes_to_a_fixed_path_in_the_checkout():
    first, min_secs = _import_with()
    second, _ = _import_with()
    assert first == second == os.path.join(REPO, ".jax_cache")
    # nothing that moves between processes: no pid, time or temp part
    assert str(os.getpid()) not in first and "tmp" not in first
    # JAX's own threshold applies; the package sets none
    import jax

    assert min_secs == jax.config.jax_persistent_cache_min_compile_time_secs


def test_cpu_pin_skips_the_cache():
    cache_dir, _ = _import_with(JAX_PLATFORMS="cpu")
    assert cache_dir == "None"
