"""Backend-readiness probe: the init-hang guard on the device plane.

A backend init that blocks (a chip held by another process) never
raises, which no exception handler can catch. These tests pin the
probe's contract: bounded wait, process-cached result, late-success
pickup, and ChunkSession raising (or degrading, under
MAKISU_TPU_CHUNK_STRICT=0) when the backend cannot come up.
"""

import threading
import time

import pytest

from makisu_tpu.ops import backend


@pytest.fixture
def fresh_probe(monkeypatch):
    """Reset the module's cached probe state around a test."""
    monkeypatch.setattr(backend, "_done", threading.Event())
    monkeypatch.setattr(backend, "_result", [None])
    monkeypatch.setattr(backend, "_started", False)
    monkeypatch.setattr(backend, "_probe_start", 0.0)
    monkeypatch.setattr(backend, "_timed_out", False)
    monkeypatch.setattr(backend, "_identity", None)
    monkeypatch.setattr(backend, "_tracker", backend._ProbeTracker())
    yield


def test_ready_on_cpu_backend(fresh_probe):
    # The test env runs the CPU backend: init is immediate.
    assert backend.backend_ready(timeout=30.0) is None
    # Cached: a second call with a tiny timeout is instant and still ok.
    assert backend.backend_ready(timeout=0.001) is None
    # What the probe learned, it keeps: the snapshot, /healthz and the
    # reports name the device as JAX reported it, not the environment.
    import jax
    want = {"platform": "cpu",
            "device_kind": jax.devices()[0].device_kind,
            "device_count": len(jax.devices())}
    assert backend.device_identity() == want
    snap = backend.probe_snapshot()
    assert {k: snap[k] for k in want} == want
    assert {k: backend.device_health()["probe"][k] for k in want} == want


def test_no_identity_before_the_backend_is_up(fresh_probe):
    assert backend.device_identity() is None
    assert "platform" not in backend.probe_snapshot()


def test_timeout_then_late_success(fresh_probe, monkeypatch):
    release = threading.Event()

    def slow_probe():
        release.wait(5.0)
        backend._result[0] = "ok"
        backend._done.set()

    monkeypatch.setattr(backend, "_probe", slow_probe)
    err = backend.backend_ready(timeout=0.05)
    assert err is not None and "did not complete" in err
    # The full bounded wait is charged ONCE per process: while still
    # pending, later calls report wedged instantly instead of waiting
    # another full timeout per layer.
    t0 = time.monotonic()
    err2 = backend.backend_ready(timeout=30.0)
    assert err2 is not None and "still pending" in err2
    assert time.monotonic() - t0 < 1.0
    # The hung init eventually finishes: later calls see ready.
    release.set()
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        if backend.backend_ready(timeout=0.5) is None:
            break
    assert backend.backend_ready(timeout=0.5) is None


def test_init_failure_is_reported(fresh_probe, monkeypatch):
    def failing_probe():
        backend._result[0] = "backend init failed: no plugin"
        backend._done.set()

    monkeypatch.setattr(backend, "_probe", failing_probe)
    err = backend.backend_ready(timeout=5.0)
    assert err == "backend init failed: no plugin"


def test_zero_timeout_disables_guard(fresh_probe, monkeypatch):
    monkeypatch.setenv("MAKISU_TPU_BACKEND_INIT_TIMEOUT", "0")
    # Guard disabled: no probe thread; init runs natively, in the
    # caller, so "ready" still means the identity is known.
    assert backend.backend_ready() is None
    assert backend._started is False
    assert backend.device_identity()["platform"] == "cpu"


def test_warm_probe_prepays_the_wait(fresh_probe, monkeypatch):
    """A process that warmed the probe early (worker startup) charges
    later backend_ready() calls only the REMAINDER of the budget."""
    release = threading.Event()

    def slow_probe():
        release.wait(5.0)
        backend._result[0] = "ok"
        backend._done.set()

    monkeypatch.setattr(backend, "_probe", slow_probe)
    backend.warm_probe()
    time.sleep(0.3)
    release.set()
    time.sleep(0.1)
    # Probe finished during the warmup window: the "first build" sees
    # ready instantly.
    t0 = time.monotonic()
    assert backend.backend_ready(timeout=30.0) is None
    assert time.monotonic() - t0 < 1.0


def test_warm_probe_remainder_budget(fresh_probe, monkeypatch):
    """With the probe warmed T seconds ago, a backend_ready(timeout)
    call waits at most (timeout - T), not a fresh full timeout."""

    def hang_probe():
        pass

    monkeypatch.setattr(backend, "_probe", hang_probe)
    backend.warm_probe()
    time.sleep(0.25)
    t0 = time.monotonic()
    err = backend.backend_ready(timeout=0.3)
    waited = time.monotonic() - t0
    assert err is not None
    assert waited < 0.2  # only the ~0.05s remainder, not a fresh 0.3s


def test_chunk_session_degrades_on_wedged_backend(monkeypatch):
    from makisu_tpu.chunker.cdc import ChunkSession

    monkeypatch.setenv("MAKISU_TPU_CHUNK_STRICT", "0")
    monkeypatch.setattr(
        backend, "backend_ready",
        lambda timeout=None: "backend init did not complete within 180s")
    s = ChunkSession()
    s.update(b"x" * (1 << 20))
    assert s.finish() == []  # degraded: no fingerprints, no hang


@pytest.mark.parametrize("strict", [None, "1"])
def test_chunk_session_raises_on_wedged_backend(monkeypatch, strict):
    """Raising is the default: an unset option is not a quiet route off
    the device."""
    from makisu_tpu.chunker.cdc import ChunkSession

    if strict is None:
        monkeypatch.delenv("MAKISU_TPU_CHUNK_STRICT", raising=False)
    else:
        monkeypatch.setenv("MAKISU_TPU_CHUNK_STRICT", strict)
    monkeypatch.setattr(
        backend, "backend_ready",
        lambda timeout=None: "backend init did not complete within 180s")
    with pytest.raises(RuntimeError, match="did not complete"):
        ChunkSession()


def test_sync_bounded_passthrough_and_timeout(monkeypatch):
    import numpy as np

    arr = np.arange(8)
    assert (backend.sync_bounded(arr, "t") == arr).all()

    class Hanging:
        def __array__(self, dtype=None, copy=None):
            time.sleep(10)
            return np.zeros(1)

    with pytest.raises(TimeoutError, match="stopped answering mid-build"):
        backend.sync_bounded(Hanging(), "gear bitmap readback",
                             timeout=0.1)


def test_sync_bounded_propagates_errors():
    class Exploding:
        def __array__(self, dtype=None, copy=None):
            raise RuntimeError("device died")

    with pytest.raises(RuntimeError, match="device died"):
        backend.sync_bounded(Exploding(), "t", timeout=5.0)


def test_chunk_session_degrades_on_readback_hang(monkeypatch):
    # Device-failure simulation: pin the XLA route (the native
    # CPU route never touches the device and cannot fail this way).
    monkeypatch.setenv("MAKISU_TPU_CHUNK_NATIVE", "0")
    from makisu_tpu.chunker import cdc

    monkeypatch.setenv("MAKISU_TPU_CHUNK_STRICT", "0")
    monkeypatch.setenv("MAKISU_TPU_SYNC_TIMEOUT", "0.2")

    real_bitmap = cdc.gear.gear_bitmap

    class HangingWords:
        def __array__(self, dtype=None, copy=None):
            time.sleep(10)

    monkeypatch.setattr(cdc.gear, "gear_bitmap",
                        lambda *a, **k: HangingWords())
    s = cdc.ChunkSession(block=64 * 1024)
    s.update(b"y" * (256 * 1024))
    assert s.finish() == []  # degraded within the bounded window
    monkeypatch.setattr(cdc.gear, "gear_bitmap", real_bitmap)
