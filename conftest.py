"""Root conftest: the test suite runs on a virtual 8-device CPU backend.

The reference validates its distributed path without a cluster by running the same
train fn at np=-1 then np=2 (SURVEY.md §4.1/§4.5); our analog is an 8-device
forced-host CPU mesh (SURVEY.md §4 "Implication for the build"). The CPU is chosen
here, by the tests' environment, before any jax backend initialises — the program
itself never defaults to it.
"""

import os
import sys

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(__file__))


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "faults: deterministic fault-injection failure-path tests "
        "(runtime.faults / GangSupervisor); run in tier-1 on CPU")
    config.addinivalue_line(
        "markers",
        "slow: long-running variants (multi-restart gangs, full-trainer "
        "fault drills) excluded from the tier-1 'not slow' selection")
