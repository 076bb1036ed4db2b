"""Test-suite wiring: platform pinning and subprocess environments.

The suite is a CPU suite (host-device meshes via XLA_FLAGS); pin
JAX_PLATFORMS before any jax import so jax does not spend a minute
probing for accelerator runtimes that are not attached.  An explicit
JAX_PLATFORMS in the environment still wins.  The entry points turn
JAX's persistent compilation cache on (``launch.compile_cache``); the
suite, and the entry points it runs, keep it off, as before.

``hypothesis`` is a REAL optional dependency: property-based tests
(test_encoding.py, test_photonics_properties.py) call
``pytest.importorskip("hypothesis")`` and skip cleanly when the package
is absent (this container); CI installs it and runs them for real.  The
old deterministic miniature stand-in that used to live here silently
downgraded the property tests to 25 fixed samples — gone.
"""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")


def subprocess_env(**extra):
    """Environment for test subprocesses (multi-device host runs): minimal
    PATH plus the same platform pin as the parent, so children skip the
    accelerator-runtime probe too.  Import from tests as
    ``from conftest import subprocess_env``."""
    env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
           "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu"),
           "JAX_ENABLE_COMPILATION_CACHE": os.environ.get(
               "JAX_ENABLE_COMPILATION_CACHE", "false")}
    env.update(extra)
    return env
