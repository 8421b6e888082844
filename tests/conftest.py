"""Test harness config.

Multi-device is faked on CPU (SURVEY §4 rebuild guidance): 8 virtual CPU
devices substitute for a TPU slice, mirroring how the reference fakes
multi-node with multi-process on localhost.

XLA_FLAGS must be set before the backend initializes.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption(
        "--runslow", action="store_true", default=False,
        help="also run @pytest.mark.slow tests (subprocess integration, "
        "large parity matrices). Default `pytest tests/` is the smoke "
        "tier; CI runs both: `pytest tests/` then `pytest tests/ "
        "--runslow`.",
    )


def pytest_collection_modifyitems(config, items):
    if config.getoption("--runslow") or os.environ.get("BYTEPS_TEST_FULL"):
        return
    skip = pytest.mark.skip(
        reason="slow tier: pass --runslow (or BYTEPS_TEST_FULL=1)")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(autouse=True)
def _fresh_config(monkeypatch):
    """Each test sees a fresh Config parsed from (possibly monkeypatched)
    env — and a fresh metrics registry / flight recorder, so telemetry
    assertions never see a sibling test's counts."""
    from byteps_tpu.common import config as config_mod
    from byteps_tpu.common.flight_recorder import reset_flight_recorder
    from byteps_tpu.common.metrics import reset_registry
    from byteps_tpu.common.tracing import reset_tracer

    def _reset():
        config_mod.reset_config()
        reset_registry()
        reset_flight_recorder()
        # the tracer's step counter otherwise leaks across tests, and
        # step-driven telemetry (flight-recorder ring) would see a
        # sibling test's step numbers
        reset_tracer()

    _reset()
    yield
    _reset()


@pytest.fixture(scope="session")
def mesh8():
    """8-device 1-D dp mesh on CPU."""
    return jax.make_mesh((8,), ("dp",))
