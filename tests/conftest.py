"""Test harness: run all JAX work on a virtual 8-device CPU mesh.

Mirrors the reference's hermetic test strategy (SURVEY.md §4): no real
registry, no real TPU needed. Env vars must be set before jax imports.
"""

import os

# Tier-1 runs on CPUs only, wherever it runs: on a machine with a chip
# an unpinned JAX would take the chip for the test process (one process
# owns a chip at a time) and trace the TPU routes, which these tests
# check in interpret mode and against the compile-only topology
# instead. XLA_FLAGS is read when the first backend initializes; the
# platform goes through jax.config as well as the environment (which
# child processes inherit), so a plugin that imported jax before this
# file cannot have fixed it already.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Compiled executables are reused across test processes: the program's
# own cache site (makisu_tpu/ops/__init__.py) places them in
# <checkout>/.jax_cache unless JAX_COMPILATION_CACHE_DIR says otherwise.
# XLA:CPU programs under half a second are cheaper to recompile than to
# write.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")
# The device-session ledger (utils/deviceprobe.py) must never write
# into the repo's benchmarks/device_sessions from a test run; tests
# that exercise it point the env var at a tmp dir explicitly.
os.environ.setdefault("MAKISU_TPU_DEVICE_SESSIONS_DIR", "")


import pytest  # noqa: E402

from makisu_tpu.utils import mountinfo  # noqa: E402


@pytest.fixture(autouse=True)
def _no_mounts():
    """Tmp build roots must not inherit the host mount table's skip
    rules (one definition for every suite; tests needing specific
    mountpoints override inside the test body)."""
    mountinfo.set_mountpoints_for_testing(set())
    yield
    mountinfo.set_mountpoints_for_testing(None)
