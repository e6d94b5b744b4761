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


@pytest.fixture
def ragged_pages():
    """``build(S, room, widen=1, dead=0) -> (table, positions)`` for the
    paged kernels' tests: six rows in one batch whose keys in the pool
    number 0 (nothing to read), 1, ``S - 1``, ``S``, ``S + 1`` and all
    that 4 pages hold but ``room`` (the step's own rows: every column
    live). Live pages are 1..9, each row's its own; the table is ``4 *
    widen`` columns wide and every dead column names page ``dead`` (a
    pool of 11 pages leaves 10 to poison)."""
    def build(S, room, widen=1, dead=0):
        positions = [0, 1, S - 1, S, S + 1, 4 * S - room]
        table = np.full((len(positions), 4 * widen), dead, np.int32)
        page = 1
        for row, n in enumerate(positions):
            live = -(-n // S)
            table[row, :live] = np.arange(page, page + live)
            page += live
        assert page == 10
        return table, positions
    return build


# the chip-compile files (tests/test_chip_compile*.py)

@pytest.fixture(scope="module")
def chip():
    """A SingleDeviceSharding on one described v5e chip."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:                       # no libtpu here
        pytest.skip("cannot describe a v5e topology: %s" % exc)
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def _persistent_cache_off():
    # a compile for a described device is written to the persistent
    # cache but cannot be read back without a chip: keep it out (of
    # every case of the chip-compile files, by their ``pytestmark``)
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()
