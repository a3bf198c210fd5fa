"""Test configuration.

* Forces CPU jax with an 8-device virtual mesh so multi-"silo" sharding tests
  run anywhere (the real four-chip path is ``chip_smoke.py --chips 4``; its
  compile for a described v5e:2x2 is kept in tests/test_chip_compile.py).
* Minimal async-test support: any ``async def test_*`` runs under
  ``asyncio.run`` (no pytest-asyncio in the image).
"""

import os

# Must run before jax backends initialize: the tests always run on the
# CPU backend, whatever the shell exports (JAX_PLATFORMS=cpu alone keeps
# the installed jax from loading libtpu).
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import asyncio  # noqa: E402
import inspect  # noqa: E402

import pytest  # noqa: E402


def pytest_configure(config):
    # tier-1 runs with `-m 'not slow'`; register the marker so slow-marked
    # soaks (rebalance convergence, chaos) don't warn
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 fast suite")


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {name: pyfuncitem.funcargs[name]
                  for name in pyfuncitem._fixtureinfo.argnames}
        asyncio.run(fn(**kwargs))
        return True
    return None
