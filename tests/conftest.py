"""Test environment: force an 8-device virtual CPU mesh so every
multi-chip sharding path is exercised without TPU hardware.

Must run before the first `import jax` anywhere in the test session.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# the config update wins over whatever platform the environment chose
# before this file ran
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: heavyweight multi-process tests"
    )


import pytest  # noqa: E402

# fluidsan (testing/sanitizer.py): FFTPU_SANITIZE=1 instruments every
# threading.Lock/RLock created during the session with the lockset
# sanitizer. Installed at conftest import — BEFORE test modules
# import — so locks created at test-module import time are wrapped
# too. jitsan (testing/jitsan.py) rides the same guard: it baselines
# the kernel jit caches and arms the donation read-traps. detsan
# (testing/detsan.py) rides it too: patched time/random entry points
# trip on un-routed clock reads / unseeded RNG draws inside
# deterministic-plane components. wiresan (testing/wiresan.py)
# completes the set: the patched pack/dispatch wire seams trip on any
# registered frame type carrying a field absent from the WIRE_SCHEMA
# registry. failsan (testing/failsan.py) is the fifth: it hooks the
# chaos plane's arm/disarm and trips when an injected fault maps to
# no observable signal (fault-to-signal accounting,
# docs/ROBUSTNESS.md). The autouse guard below fails any test that
# trips any of the five.
_SANITIZE = os.environ.get("FFTPU_SANITIZE") == "1"
if _SANITIZE:
    from fluidframework_tpu.testing import detsan as _detsan
    from fluidframework_tpu.testing import failsan as _failsan
    from fluidframework_tpu.testing import jitsan as _jitsan
    from fluidframework_tpu.testing import sanitizer as _fluidsan
    from fluidframework_tpu.testing import wiresan as _wiresan

    _fluidsan.install()
    _jitsan.install()
    _detsan.install()
    _wiresan.install()
    _failsan.install()


@pytest.fixture(autouse=True)
def _fluidsan_trip_guard():
    if not _SANITIZE:
        yield
        return
    from fluidframework_tpu.testing import (
        detsan, failsan, jitsan, sanitizer, wiresan,
    )

    before = len(sanitizer.trips())
    before_jit = len(jitsan.trips())
    before_det = len(detsan.trips())
    before_wire = len(wiresan.trips())
    before_fail = len(failsan.trips())
    yield
    fresh = sanitizer.trips()[before:]
    if fresh:
        pytest.fail(
            "fluidsan tripped during this test:\n"
            + "\n".join(t.describe() for t in fresh)
            + "\n" + fresh[0].flight_dump
        )
    fresh_jit = jitsan.trips()[before_jit:]
    if fresh_jit:
        pytest.fail(
            "jitsan tripped during this test:\n"
            + "\n".join(t.describe() for t in fresh_jit)
        )
    fresh_det = detsan.trips()[before_det:]
    if fresh_det:
        pytest.fail(
            "detsan tripped during this test:\n"
            + "\n".join(t.describe() for t in fresh_det)
            + "\n" + fresh_det[0].flight_dump
        )
    fresh_wire = wiresan.trips()[before_wire:]
    if fresh_wire:
        pytest.fail(
            "wiresan tripped during this test:\n"
            + "\n".join(t.describe() for t in fresh_wire)
        )
    # trips() evaluates any window closed during this test — the
    # chaos harnesses disarm before quiesce, so teardown is the first
    # point where every recovery signal has landed
    fresh_fail = failsan.trips()[before_fail:]
    if fresh_fail:
        pytest.fail(
            "failsan tripped during this test:\n"
            + "\n".join(t.describe() for t in fresh_fail)
        )


@pytest.fixture()
def mesh_cpu_subprocess():
    """Run a python snippet in a subprocess pinned to a 4-device
    virtual CPU mesh (JAX_PLATFORMS=cpu +
    XLA_FLAGS=--xla_force_host_platform_device_count=4) — the
    mesh-pool suite's multi-shard paths execute on CPU-only CI
    regardless of how the PARENT session configured its devices
    (bench config10 emulates shards the same way). The env is
    subprocess-scoped: nothing leaks into this process, whose jax is
    already initialized."""
    import subprocess
    import sys

    def run(code: str, timeout: float = 300.0) -> str:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        # the child asserts its own invariants; the session sanitizer
        # belongs to THIS process's conftest guard, not the child
        env.pop("FFTPU_SANITIZE", None)
        proc = subprocess.run(
            [sys.executable, "-c", code], capture_output=True,
            text=True, timeout=timeout,
            cwd=os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))),
            env=env)
        assert proc.returncode == 0, (
            f"mesh subprocess failed rc={proc.returncode}:\n"
            f"{proc.stderr[-2000:]}"
        )
        return proc.stdout

    return run


@pytest.fixture()
def alfred(monkeypatch):
    """AlfredServer on a background event loop — ONE definition for
    every wire-level test file. ``start(tenants=..., 
    server_versions=...)`` returns the running server; teardown stops
    it and joins the thread."""
    import asyncio
    import threading

    state = {}

    def start(tenants=None, server_versions=None, qos=None,
              slo=None):
        from fluidframework_tpu.service import ingress as ingress_mod
        from fluidframework_tpu.service.ingress import AlfredServer

        if server_versions is not None:
            monkeypatch.setattr(
                ingress_mod, "WIRE_VERSIONS", tuple(server_versions))
        server = AlfredServer(tenants=tenants, qos=qos, slo=slo)
        loop = asyncio.new_event_loop()
        started = threading.Event()

        def run():
            asyncio.set_event_loop(loop)
            loop.run_until_complete(server.start())
            started.set()
            loop.run_forever()

        t = threading.Thread(target=run, daemon=True)
        t.start()
        assert started.wait(10)
        state.update(server=server, loop=loop, thread=t)
        return server

    yield start
    if state:
        import asyncio

        fut = asyncio.run_coroutine_threadsafe(
            state["server"].stop(), state["loop"])
        try:
            fut.result(timeout=10)
        except Exception:
            pass
        state["loop"].call_soon_threadsafe(state["loop"].stop)
        state["thread"].join(timeout=10)
