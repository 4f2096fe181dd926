"""Test-session config: keep JAX on the single host device (the 512-device
forcing is ONLY for the dry-run entry points), relax hypothesis deadlines on
loaded CI machines."""
import os

from hypothesis import settings

# Guard: tests must see exactly one device — dryrun/costmodel set XLA_FLAGS
# themselves and run as separate processes.
os.environ.pop("XLA_FLAGS", None)

settings.register_profile("repro", deadline=None, derandomize=True)
settings.load_profile("repro")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-process fault-injection tests (subprocess "
        "JAX compiles); deselect with -m 'not slow'")
