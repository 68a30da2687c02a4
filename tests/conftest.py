"""Test configuration: force an 8-device virtual CPU mesh BEFORE any jax
backend initializes (SURVEY.md §4: reference proves distributed logic with
single-host multi-process + CPU collectives; here it's jax CPU devices).
The CPU platform is forced through jax.config before first device use."""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
if "--xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

try:
    jax.config.update("jax_platforms", "cpu")
except Exception:
    pass

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from tier-1")
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection tests (in-process, deterministic, <10s "
        "each — tier-1)")


def pytest_collection_modifyitems(config, items):
    """Chaos-marker guard: any test in a module that imports the
    fault-injection harness at module level MUST carry the ``chaos``
    marker (so ``pytest -m chaos`` really runs the whole chaos tier and
    ``-m 'not chaos'`` really excludes it). Fails collection otherwise."""
    import types
    unmarked = []
    for item in items:
        mod = getattr(item, "module", None)
        if mod is None:
            continue
        uses_fi = any(
            isinstance(v, types.ModuleType)
            and getattr(v, "__name__", "")
            == "paddle_tpu.utils.fault_injection"
            for v in vars(mod).values())
        if uses_fi and item.get_closest_marker("chaos") is None:
            unmarked.append(item.nodeid)
    if unmarked:
        raise pytest.UsageError(
            "tests built on paddle_tpu.utils.fault_injection must be "
            "@pytest.mark.chaos (or mark the module: pytestmark = "
            "pytest.mark.chaos):\n  " + "\n  ".join(sorted(unmarked)))


@pytest.fixture(autouse=True)
def _seed_rng():
    import paddle_tpu
    paddle_tpu.seed(1234)
    yield


@pytest.fixture(autouse=True)
def _restore_hybrid_mesh():
    """Process-global mesh hygiene: a test that calls ``fleet.init``
    (or sets the HybridCommunicateGroup directly) must not leak its
    mesh into later modules — that is exactly the order-dependent
    failure class where test_metrics' default-'world'-mesh collective
    counters saw test_models' hybrid mesh. Each test still SEES
    whatever was set before it (behavior unchanged mid-test); the
    snapshot/restore only guarantees the leak stops at the test
    boundary."""
    from paddle_tpu.distributed import topology
    prev = topology.get_hybrid_communicate_group()
    yield
    topology.set_hybrid_communicate_group(prev)
