"""Test bootstrap: run JAX on a virtual 8-device CPU mesh.

Tests run on the CPU backend with eight virtual devices (multi-chip
sharding included); the chip is reached through chip_smoke.py. The CPU
platform is forced through jax.config, so it holds whatever the
environment says; XLA_FLAGS must be set before the backend initializes.
"""

import os
import sys

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Unit tests exercise the daemon's API read plane deterministically; real
# kernel FUSE mounts are covered by tests/test_fusedev.py, which re-enables
# this in its subprocess daemons.
os.environ.setdefault("NTPU_DISABLE_FUSE", "1")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: slow chaos/e2e sweeps excluded from tier-1 (-m 'not slow')"
    )


def pytest_sessionfinish(session, exitstatus):
    """Lockset race gate: with NTPU_ANALYZE=1 (the CI analyze job runs
    the stress suites under it), any race or lock-order cycle the runtime
    detector recorded fails the whole session."""
    from nydus_snapshotter_tpu.analysis import runtime as _an

    if not _an.ENABLED:
        return
    report = _an.report()
    if report:
        print("\nNTPU_ANALYZE runtime findings:\n" + report, file=sys.stderr)
        session.exitstatus = 3
