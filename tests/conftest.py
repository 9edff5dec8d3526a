"""Test configuration.

Distributed tests run on a virtual 8-device CPU mesh (the reference tests
multi-node behavior with real mini-clusters on one machine,
src/test/opentenbase_test/ — our analog is N jax CPU devices standing in for
N datanode chips).  These env vars must be set before jax is imported.
"""

import os
import sys

# Force, don't setdefault: tests are hermetic on the CPU backend whatever
# platform the environment names.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax

# jax may have been imported (by a pytest plugin) before this file ran,
# in which case it captured the environment's JAX_PLATFORMS at import.
jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running end-to-end arms "
        "(deselected by the tier-1 run)")
