# NOTE: no XLA_FLAGS / device-count overrides here — smoke tests and benches
# must see the real single CPU device.  Distributed tests spawn subprocesses
# that set --xla_force_host_platform_device_count themselves.
import os
import sys

# tests never write a persistent compilation cache, not even through the
# CLIs they run in subprocesses (which inherit this)
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

jax.config.update("jax_enable_x64", False)

import importlib

import pytest

_disp = importlib.import_module("repro.core.dispatch")
_kops = importlib.import_module("repro.kernels.ops")
_routing = importlib.import_module("repro.tune.routing")
_conv = importlib.import_module("repro.core.convert")
_obs_trace = importlib.import_module("repro.obs.trace")
_obs_registry = importlib.import_module("repro.obs.registry")


@pytest.fixture(autouse=True)
def _reset_routing_state():
    """Counter/table hygiene: every test starts with empty dispatch and
    kernel counters, an empty conversion log, no active tuning table, an
    empty telemetry registry, and the flight recorder off and empty, so a
    test asserting exact counts (or default routing) can never be
    poisoned by whatever traced before it — see
    tests/test_counter_hygiene.py for the regressions pinning this.
    ``REGISTRY.reset()`` clears metric objects *in place*, so
    module-held references (dispatch/kernel counter families, engine
    stats mirrors) stay live across the reset."""
    _disp.reset_dispatch_counters()
    _kops.reset_kernel_counters()
    _routing.clear_active_table()
    _conv.reset_conversion_log()
    _obs_registry.REGISTRY.reset()
    _obs_trace.reset()
    yield
