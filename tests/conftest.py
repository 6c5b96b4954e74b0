"""Test harness configuration.

Unit tests run hermetically on CPU with 8 virtual XLA devices so the
multi-device sharding paths compile and execute without TPU hardware
(the driver dry-runs the multi-chip path the same way).  Benchmarks run
separately on the real chip via bench.py.
"""

import os

xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Pin the CPU platform, in the environment (inherited by the servers the
# out-of-process tests boot) and in-process.  Set
# THROTTLECRAB_TPU_TEST_REAL=1 to run the suite on whatever backend the
# environment provides instead.
import throttlecrab_tpu  # noqa: E402,F401  (enables x64 before any tracing)

if not os.environ.get("THROTTLECRAB_TPU_TEST_REAL"):
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
    # The Pallas kernel compiles only for a TPU: on the CPU backend the
    # suite asks for interpret mode explicitly (the program never
    # infers it from the backend).
    from throttlecrab_tpu.tpu import pallas_fused

    pallas_fused.INTERPRET = True

# No persistent compile cache from the tests: not in this process, and
# not in the servers the out-of-process tests boot (the env is inherited;
# throttlecrab_tpu.compile_cache honours it).
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")


def require_devices(n: int) -> None:
    """Skip the calling test when the backend exposes fewer than `n`
    devices — only happens under THROTTLECRAB_TPU_TEST_REAL on
    single-chip hardware (the default CPU harness always has 8 virtual
    devices).  make_mesh(n) raises in that situation rather than
    silently shrinking the mesh."""
    import jax
    import pytest

    have = len(jax.devices())
    if have < n:
        pytest.skip(f"needs {n} devices, backend has {have}")
