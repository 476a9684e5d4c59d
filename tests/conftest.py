import os
import sys

# Tests run on the CPU, named here so the device engine's first-touch guard
# (diamond_types_tpu/tpu/runtime.py) lets them, with a virtual 8-device mesh
# for the sharding tests. The chip is chip_smoke.py's and the benchmark's.
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8").strip()

# Pin the platform via the config API too, for a jax that was imported
# before this file ran (must precede the backend's initialisation, i.e.
# any jax.devices() call).
try:
    import jax
    jax.config.update("jax_platforms", "cpu")
except ImportError:
    pass

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REFERENCE_DIR = "/root/reference"


def reference_path(*parts):
    return os.path.join(REFERENCE_DIR, *parts)


import pytest


@pytest.fixture(autouse=True)
def _fresh_engine_policy():
    """Engine-selection measurements must not leak across tests: a zone
    rate recorded by one test could otherwise flip (or probe-flip) an
    unrelated later test's Branch.merge onto the zone engine — an
    ordering-dependent flake and, on big corpora, a CPU-backend stall."""
    from diamond_types_tpu.listmerge import policy
    saved = policy.GLOBAL
    policy.GLOBAL = policy.EnginePolicy()
    yield
    policy.GLOBAL = saved
