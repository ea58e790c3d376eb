"""Test fixtures.

JAX is forced onto a virtual 8-device CPU platform (the reference's
"multi-node cluster in one machine" fixture idea, cluster_utils.py:135,
applied to SPMD: XLA_FLAGS=--xla_force_host_platform_device_count=8).
Must run before the first jax import in the test process.
"""

from ray_tpu._private.platform import force_cpu_platform

force_cpu_platform(8)

import pytest  # noqa: E402


@pytest.fixture
def ray_start_regular():
    """Boot a 1-node runtime per test (reference: conftest.py:588)."""
    import ray_tpu
    rt = ray_tpu.init(num_nodes=1, resources={"CPU": 8})
    yield rt
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    """Boot a 4-node virtual cluster (reference: conftest.py:678)."""
    import ray_tpu
    rt = ray_tpu.init(num_nodes=4, resources={"CPU": 4})
    yield rt
    ray_tpu.shutdown()


@pytest.fixture(autouse=True)
def _cleanup_runtime():
    yield
    import ray_tpu
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()


@pytest.hookimpl(tryfirst=True)
def pytest_collection_modifyitems(config, items):
    """Every collected test's marker names, taken before ``-m``
    deselects any of them (tests/test_tiers.py reads it)."""
    config.collected_marks = {
        item.nodeid: {m.name for m in item.iter_markers()}
        for item in items}


def pytest_sessionfinish(session, exitstatus):
    """Lock-sanitizer gate (tools/run_chaos.sh sanitized stage): the
    -W error escalation only fails tests whose inversion fires on the
    MAIN thread; most runtime locks are acquired on daemon threads,
    where a raised LockOrderViolation dies with the thread. The graph
    records every violation regardless of thread — fail the session on
    any of them when the sanitizer is armed."""
    import os
    if os.environ.get("RAY_TPU_LOCK_SANITIZER") != "1":
        return
    try:
        from ray_tpu._private.lock_sanitizer import GRAPH
    except Exception:
        return
    if GRAPH.violations and exitstatus == 0:
        reporter = session.config.pluginmanager.get_plugin(
            "terminalreporter")
        if reporter is not None:
            reporter.write_line(
                f"lock sanitizer: {len(GRAPH.violations)} lock-order "
                f"violation(s) recorded on runtime threads:", red=True)
            for v in GRAPH.violations:
                reporter.write_line(v, red=True)
        # pytest.exit from sessionfinish is the sanctioned way to force
        # the process exit code (wrap_session catches exit.Exception
        # and adopts its returncode; plain session.exitstatus
        # assignment does not stick here)
        pytest.exit("lock-order violations recorded", returncode=1)
