"""Test configuration: force JAX onto a virtual 8-device CPU platform so the
full stack (including multi-chip sharding) runs without TPU hardware.

Must set the env vars before jax is imported anywhere in the test process.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

assert jax.device_count() == 8, (
    "tests need 8 virtual CPU devices (got {}): XLA_FLAGS "
    "--xla_force_host_platform_device_count=8 did not take — jax was "
    "initialized before conftest ran".format(jax.device_count())
)

import pytest  # noqa: E402


def pytest_configure(config):
    # the repo has no pytest.ini/pyproject marker section; register the
    # tier-1 exclusion marker here so `-m 'not slow'` runs warning-free
    config.addinivalue_line(
        "markers", "slow: long-running test excluded from the tier-1 run"
    )
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection robustness test (CPU-fast, runs in tier-1; "
        "select with -m chaos)",
    )


def pytest_collection_finish(session):
    # chip_smoke's tiny walk is a minute of child processes: start it now
    # so it overlaps the first test files (tests/test_chip_smoke.py joins it)
    if session.config.option.collectonly:
        return
    for item in session.items:
        if item.name.startswith("test_tiny_size_walks_every_phase"):
            item.module.start_tiny_walk()


@pytest.fixture()
def state_root(tmp_path):
    """Isolated control-plane state root per test."""
    root = tmp_path / "state"
    os.environ["TPUSERVE_STATE_ROOT"] = str(root)
    yield root
    os.environ.pop("TPUSERVE_STATE_ROOT", None)


def fill_pages(cache, slot, k, v, k_scale=None, v_scale=None, *, append=False):
    """Test reference for putting token K/V into a slot's pages, for tests
    of what READS a pool (kernels, tiers, shipment): ``k``/``v`` are stacked
    [L, S, Hkv, D] (scales [L, S, Hkv] on int8 pools) and land at the
    coordinates ``pool.token_coords`` names, by plain ``.at[].set``. The
    slot is started anew, or with ``append`` grown by S tokens. The serving
    path never fills pages from the host: its launches write them."""
    import jax.numpy as jnp

    pool = cache.pool
    n = int(k.shape[1])
    if append:
        start = pool.slot_length(slot)
        pool.extend(slot, n)
        cache.apply_pending_cow()
    else:
        start = 0
        pool.free(slot)
        pool.allocate(slot, n)
    pages, offsets = (
        jnp.asarray(c) for c in zip(*pool.token_coords(slot, start, n))
    )
    for name, rows in (("k", k), ("v", v),
                       ("k_scale", k_scale), ("v_scale", v_scale)):
        if rows is not None:
            buf = getattr(cache, name)
            setattr(cache, name, buf.at[:, :, pages, offsets].set(
                jnp.moveaxis(jnp.asarray(rows, buf.dtype), 1, 2)
            ))
