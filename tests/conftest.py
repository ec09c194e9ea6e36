"""Test configuration: force an 8-device virtual CPU mesh so distributed
(sharding/collective) paths run without TPU hardware, mirroring the
reference's local-cluster-mode test vehicle (SURVEY.md §4 tier 3).

JAX's persistent compilation cache stays off for tests (and for the
child interpreters they start): nothing a test compiles should land in
the checkout's ``.jax_cache`` (exec/pcache.py place_jax_cache).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: exhaustive suites excluded from the tier-1 budget "
        "(run explicitly with -m slow)")
