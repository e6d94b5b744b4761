"""Test configuration.

Per SURVEY §4: the suite runs on a virtual 8-device CPU mesh so sharding
and collective paths are exercised without TPU hardware (the reference's
analogous trick is multi-process single-host launch of dist kvstore
tests). Env vars MUST be set before jax is imported anywhere.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
prev = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in prev:
    os.environ["XLA_FLAGS"] = \
        (prev + " --xla_force_host_platform_device_count=8").strip()

import numpy as np
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running smoke tests excluded from the tier-1 run")


@pytest.hookimpl(tryfirst=True, hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    rep = outcome.get_result()
    setattr(item, "rep_" + rep.when, rep)


@pytest.fixture(autouse=True)
def _seed_all(request):
    """Seed numpy + framework RNG per test and print repro info on failure
    (reference: tests/python/unittest/common.py @with_seed)."""
    seed = int(os.environ.get("MXNET_TEST_SEED", "0")) or \
        int(np.random.randint(0, 2**31))
    np.random.seed(seed)
    import mxnet_tpu as mx
    mx.random.seed(seed)
    yield
    rep = getattr(request.node, "rep_call", None)
    if rep is not None and rep.failed:
        print("\nTo reproduce: MXNET_TEST_SEED=%d" % seed)
