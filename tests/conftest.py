"""Test configuration.

Tests run CPU-only with a virtual 8-device mesh so multi-chip sharding paths
compile and execute without TPU hardware (mirrors the reference's strategy of
CPU-only full-graph tests with echo engines, SURVEY.md §4). Env must be set
before any jax import.

Async tests: plain `async def test_*` functions are run in a fresh event loop
(no pytest-asyncio dependency). Use the async context-manager helpers in
`tests/helpers.py` for hub/runtime fixtures.
"""

import os

# Tests never touch an accelerator: force the CPU backend and eight
# virtual devices through the environment, before anything imports jax.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("DYN_LOG", "warn")

import asyncio  # noqa: E402
import inspect  # noqa: E402


def pytest_pyfunc_call(pyfuncitem):
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        asyncio.run(asyncio.wait_for(fn(**kwargs), timeout=120))
        return True
    return None
