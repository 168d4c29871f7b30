"""Test config: run JAX on a virtual 8-device CPU mesh.

Tests run on the CPU (``JAX_PLATFORMS=cpu``) and validate semantics and
sharding on a host-platform device mesh. On the chip, the benchmark
cells (``benchmark/run.py``) and ``python chip_smoke.py`` (the paths no
cell runs yet) drive the serving paths, and tests/test_tpu_compile.py
compiles the kernels for a described v5e.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Arm the debug-tier truncation guard in ops/mergetree_blocks.to_flat
# (a host-syncing max(count) readback, off on the serving hot path) —
# the suite keeps the tripwire while production stays async. Must be
# set before fluidframework_tpu.ops.mergetree_blocks is imported.
os.environ.setdefault("FFTPU_DEBUG_TO_FLAT", "1")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from fluidframework_tpu.utils import compile_cache  # noqa: E402

# Farms recompile every pool-bucket shape from scratch on a cold run;
# the persistent cache makes re-runs (and the soak/farm tiers) pay XLA
# compilation once per shape per machine instead of once per session.
compile_cache.enable()

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reap_cluster_children():
    """Subprocess hygiene: any cluster child spawned through
    tools/launch_cluster during a test is reaped at teardown even when
    the test failed or timed out mid-launch — CI must never accumulate
    orphaned follower/replica processes. Free for the rest of the
    suite (one sys.modules lookup)."""
    import sys

    yield
    mod = sys.modules.get("fluidframework_tpu.tools.launch_cluster")
    if mod is not None:
        mod.reap_all()


@pytest.fixture(scope="session")
def cpu_mesh_devices():
    import jax

    devices = jax.devices()
    assert len(devices) >= 8, f"expected >=8 virtual devices, got {len(devices)}"
    return devices


@pytest.fixture()
def secure_alfred():
    """In-process AlfredServer with auth + tight throttling on a loop
    thread; yields (port, tenant)."""
    import asyncio
    import threading

    from fluidframework_tpu.server.alfred import AlfredServer
    from fluidframework_tpu.server.riddler import TenantManager, Throttler
    from fluidframework_tpu.server.routerlicious import RouterliciousService

    tenants = TenantManager()
    tenant = tenants.create_tenant("acme")
    service = RouterliciousService()
    server = AlfredServer(service, tenants=tenants,
                          throttler=Throttler(rate_per_interval=50,
                                              interval_s=60.0))
    loop = asyncio.new_event_loop()
    started = threading.Event()

    async def run():
        await server.start()
        started.set()

    thread = threading.Thread(target=lambda: (
        loop.run_until_complete(run()), loop.run_forever()), daemon=True)
    thread.start()
    assert started.wait(10)
    try:
        yield server.port, tenant
    finally:
        # Best-effort teardown: stop listening, stop the loop. Connection
        # handler tasks die with the daemon thread (py3.12's wait_closed
        # would block on any handler still parked in a read).
        loop.call_soon_threadsafe(
            lambda: server._server is not None and server._server.close())
        loop.call_soon_threadsafe(loop.stop)
        thread.join(10)
