"""Test harness config: run everything on a virtual 8-device CPU mesh.

Multi-chip hardware is not available in CI; sharding paths are validated on
``--xla_force_host_platform_device_count=8`` per the project test strategy
(the driver separately dry-run-compiles the multichip path via
``__graft_entry__.dryrun_multichip``).

``JAX_PLATFORMS`` is forced to ``cpu`` here, before the backend is
initialized (hence this top-level conftest): the suite never needs a chip.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# The runtime sanitizers raise at the violation site in every suite: a lock
# samples the mode when it is created, so it is set before the package is
# imported, and child processes the tests start inherit it.
os.environ.setdefault("SRJT_SANITIZE", "strict")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# Persistent executable cache: the per-module clear_caches below drops
# live executables to bound XLA:CPU memory, so heavyweight programs
# (capture/replay traces, fused scans, the mortgage ETL) recompile once
# per module — with the disk cache those recompiles deserialize instead,
# keyed on HLO, across modules AND runs.  The directory follows the one
# rule every entry point shares (utils/compile_cache.py).
from spark_rapids_jni_tpu.utils import compile_cache  # noqa: E402

compile_cache.configure()


import gc

import pytest


@pytest.fixture(scope="module", autouse=True)
def _clear_jax_caches_per_module():
    """Cap compiled-executable memory across the (large) suite: two full
    runs segfaulted inside XLA:CPU's backend_compile around the ~85% mark
    with hundreds of live executables; dropping caches between modules
    trades some recompiles for a bounded footprint."""
    yield
    jax.clear_caches()
    gc.collect()
