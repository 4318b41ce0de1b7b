"""Test harness config: force an 8-device virtual CPU mesh (SURVEY.md §4 takeaway (2):
the reference simulates multi-node by multi-process-on-localhost; here SPMD sharding is
validated on host devices the same way the driver's dryrun does).
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "quick: fast core tier (op sweep + parallelism oracles; "
        "run with -m quick, or -m quick -n 4 for <5 min)")
    config.addinivalue_line(
        "markers", "slow: heavyweight (wheel builds, large compiles)")
    config.addinivalue_line(
        "markers",
        "faults: deterministic fault-injection suite (checkpoint commit "
        "protocol, store deadlines, server degradation, self-healing "
        "training) — call-count-keyed schedules, no wall-clock dependence")
