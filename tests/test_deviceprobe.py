"""Device-route init observability: phase-resolved probe, stack-sample
trajectory, the deviceprobe.v1 session ledger, `doctor --device`, and
the device execution telemetry (dispatch rings, H2D, padding waste).

The acceptance shape: a simulated backend-init wedge — a probe thread
parked in an uninterruptible call, the shape of an init waiting on a
chip another process holds — must produce a ledger record naming the
wedged PHASE with a non-empty stack-sample trajectory, and `doctor
--device` must render a diagnosis from it.
"""

import os
import threading
import time

import numpy as np
import pytest

from makisu_tpu.ops import backend
from makisu_tpu.utils import deviceprobe, events, metrics


@pytest.fixture
def fresh_probe(monkeypatch):
    monkeypatch.setattr(backend, "_done", threading.Event())
    monkeypatch.setattr(backend, "_result", [None])
    monkeypatch.setattr(backend, "_started", False)
    monkeypatch.setattr(backend, "_probe_start", 0.0)
    monkeypatch.setattr(backend, "_timed_out", False)
    monkeypatch.setattr(backend, "_identity", None)
    monkeypatch.setattr(backend, "_tracker", backend._ProbeTracker())
    yield


def _wait_for(predicate, timeout=8.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        result = predicate()
        if result:
            return result
        time.sleep(0.02)
    return predicate()


# -- the wedge golden path --------------------------------------------------


def _wedge_in_native_call(release: threading.Event) -> None:
    """Stand-in for the C-level wedge: the thread blocks in an
    uninterruptible wait (a semaphore park inside the interpreter's C
    layer — no Python line ever raises, exactly like
    make_c_api_client). The function NAME is the assertion target: the
    stack sampler must surface it."""
    release.wait(30.0)


def _hanging_client_init(release: threading.Event):
    """A client_init phase that wedges until ``release`` — and then
    completes CORRECTLY, so the released probe thread finishing can
    only ever flip the module state to ok."""
    def phase(ctx):
        _wedge_in_native_call(release)
        ctx["devices"] = ctx["jax"].devices()
    return phase


def _drain_probe_threads(release: threading.Event) -> None:
    """Release the simulated wedge and JOIN the probe thread(s) while
    this test's monkeypatched module state is still current — a probe
    finishing after teardown would set the NEXT test's fresh _done, and
    its watcher would append the terminal (``*_late``) record to the
    NEXT test's ledger directory."""
    release.set()
    for t in threading.enumerate():
        if t.name in ("jax-backend-probe", "jax-probe-watch"):
            t.join(timeout=15)


def test_simulated_wedge_produces_ledger_record(fresh_probe,
                                                monkeypatch, tmp_path):
    """Acceptance: a backend-init wedge yields a deviceprobe.v1 record
    naming the wedged phase with >=3 stack samples, and doctor
    --device renders a diagnosis from it."""
    release = threading.Event()
    sessions = tmp_path / "sessions"
    monkeypatch.setenv("MAKISU_TPU_DEVICE_SESSIONS_DIR", str(sessions))
    monkeypatch.setenv("MAKISU_TPU_PROBE_SAMPLE_INTERVAL", "0.02")
    monkeypatch.setenv("MAKISU_TPU_PROBE_TIMEOUT", "0.5")
    monkeypatch.setattr(backend, "_phase_client_init",
                        _hanging_client_init(release))
    try:
        err = backend.backend_ready(source="worker")
        assert err is not None and "did not complete" in err

        records = _wait_for(
            lambda: deviceprobe.read_records(str(sessions)))
        assert records, "wedge never produced a ledger record"
        rec = records[-1]
        assert rec["schema"] == "makisu-tpu.deviceprobe.v1"
        assert rec["verdict"] == "wedged"
        assert rec["source"] == "worker"
        assert rec["wedged_phase"] == "client_init"
        # Plugin discovery COMPLETED before the wedge: the record
        # carries the per-phase timing that proves it.
        done_phases = {p["phase"] for p in rec["phases"] if p["ok"]}
        assert "plugin_discovery" in done_phases
        assert rec["phase_reached"] == "plugin_discovery"
        # Non-empty trajectory, >=3 samples, naming the parked frame.
        assert rec["samples"]
        assert sum(s["count"] for s in rec["samples"]) >= 3
        assert any("_wedge_in_native_call" in s["frame"]
                   for s in rec["samples"])
        # The attachment fingerprint is hashed — raw endpoint values
        # must not land in the shared artifact.
        assert len(rec["attachment"]["key"]) == 32

        # The live snapshot agrees (what /healthz and bundles serve).
        snap = backend.probe_snapshot()
        assert snap["state"] == "wedged"
        assert snap["phase"] == "client_init"
        assert snap["sample_count"] >= 3
        assert "_wedge_in_native_call" in snap["deepest_frame"]

        # Golden: the cross-session doctor names phase and frame.
        out = deviceprobe.render_device_doctor(records)
        assert "dominant wedge: phase 'client_init'" in out
        assert "_wedge_in_native_call" in out
        assert "identical samples" in out
        assert "diagnosis: backend init wedges in 'client_init'" in out
    finally:
        _drain_probe_threads(release)


def test_doctor_device_cli_renders_wedge(fresh_probe, monkeypatch,
                                         tmp_path, capsys):
    release = threading.Event()
    sessions = tmp_path / "sessions"
    monkeypatch.setenv("MAKISU_TPU_DEVICE_SESSIONS_DIR", str(sessions))
    monkeypatch.setenv("MAKISU_TPU_PROBE_SAMPLE_INTERVAL", "0.02")
    monkeypatch.setenv("MAKISU_TPU_PROBE_TIMEOUT", "0.4")
    monkeypatch.setattr(backend, "_phase_client_init",
                        _hanging_client_init(release))
    try:
        assert backend.backend_ready() is not None
        assert _wait_for(
            lambda: deviceprobe.read_records(str(sessions)))
        from makisu_tpu import cli
        assert cli.main(["doctor", "--device", str(sessions)]) == 0
        out = capsys.readouterr().out
        assert "device route" in out
        assert "client_init" in out
    finally:
        _drain_probe_threads(release)


def test_doctor_device_cli_errors_on_empty(monkeypatch, tmp_path):
    from makisu_tpu import cli
    monkeypatch.setenv("MAKISU_TPU_DEVICE_SESSIONS_DIR",
                       str(tmp_path / "empty"))
    with pytest.raises(SystemExit, match="no makisu-tpu.deviceprobe"):
        cli.main(["doctor", "--device"])
    with pytest.raises(SystemExit, match="bundle path"):
        cli.main(["doctor"])


# -- the healthy path -------------------------------------------------------


def test_healthy_probe_records_ok_with_phase_timings(fresh_probe,
                                                     monkeypatch,
                                                     tmp_path):
    """On the XLA-CPU backend every phase completes: the ledger record
    carries all five phase timings and verdict ok — the healthy-path
    record CI smokes and future device sessions diff against."""
    sessions = tmp_path / "sessions"
    monkeypatch.setenv("MAKISU_TPU_DEVICE_SESSIONS_DIR", str(sessions))
    assert backend.backend_ready(timeout=120.0) is None
    assert backend.wait_for_probe_record(20.0)
    records = deviceprobe.read_records(str(sessions))
    assert records
    rec = records[-1]
    assert rec["verdict"] == "ok"
    assert rec["wedged_phase"] == ""
    assert rec["phase_reached"] == "first_dispatch"
    assert [p["phase"] for p in rec["phases"]] == \
        list(backend.PROBE_PHASES)
    assert all(p["ok"] for p in rec["phases"])
    assert all(p["seconds"] >= 0 for p in rec["phases"])

    snap = backend.probe_snapshot()
    assert snap["state"] == "ok"
    assert backend.probe_label() == "ok"

    out = deviceprobe.render_device_doctor(records)
    assert "healthy" in out
    assert "first_dispatch" not in out.split("diagnosis:")[1]


def test_probe_phase_events_on_event_bus(fresh_probe, monkeypatch):
    """Each phase emits start/done heartbeats on the event bus."""
    seen: list[dict] = []
    events.add_global_sink(seen.append)
    try:
        assert backend.backend_ready(timeout=120.0) is None
    finally:
        events.remove_global_sink(seen.append)
    phases = [(e.get("phase"), e.get("status")) for e in seen
              if e.get("type") == "device_probe"]
    for name in backend.PROBE_PHASES:
        assert (name, "start") in phases
        assert (name, "done") in phases
    # Phases stream in execution order.
    starts = [p for p, s in phases if s == "start"]
    assert starts == list(backend.PROBE_PHASES)


def test_probe_snapshot_absent_and_disabled(fresh_probe, monkeypatch):
    assert backend.probe_snapshot()["state"] == "absent"
    assert backend.probe_label() == "absent"
    monkeypatch.setenv("MAKISU_TPU_PROBE_TIMEOUT", "0")
    assert backend.probe_snapshot()["state"] == "disabled"


def test_recording_gated_off_without_device_config(fresh_probe,
                                                   monkeypatch):
    """With no explicit sessions dir and no device configured (the
    plain CPU test environment), probe attempts must not write into
    the repo's benchmarks/device_sessions."""
    monkeypatch.delenv("MAKISU_TPU_DEVICE_SESSIONS_DIR", raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    for var in list(os.environ):
        if var.startswith(backend.ATTACHMENT_ENV_PREFIXES):
            monkeypatch.delenv(var, raising=False)
    assert backend._recording_wanted() is False
    # A device platform flips the gate on...
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    assert backend._recording_wanted() is True
    # ...as does an attachment var when no platform is pinned
    # (JAX_PLATFORMS=cpu explicitly gates off, same as the worker's
    # warm-probe rule — a cpu-pinned process is not a device attempt).
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-1")
    assert backend._recording_wanted() is True
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert backend._recording_wanted() is False
    # Explicit env var always wins (CI's healthy-path cpu smoke).
    monkeypatch.setenv("MAKISU_TPU_DEVICE_SESSIONS_DIR", "")
    assert backend._recording_wanted() is False
    monkeypatch.setenv("MAKISU_TPU_DEVICE_SESSIONS_DIR", "/tmp/x")
    assert backend._recording_wanted() is True


# -- ledger + doctor units --------------------------------------------------


def _record(ts, verdict, phase="client_init", source="build",
            key="a" * 32, frame="make_c_api_client (xla_bridge.py:123)",
            count=12):
    rec = {
        "schema": deviceprobe.SCHEMA, "ts": ts, "pid": 1,
        "source": source, "platform": "tpu",
        "attachment": {"key": key, "vars": ["TPU_ENDPOINT"]},
        "verdict": verdict, "detail": "", "timeout_seconds": 300,
        "total_seconds": 300.0 if verdict == "wedged" else 18.0,
        "phase_reached": ("first_dispatch" if verdict == "ok"
                          else "plugin_discovery"),
        "wedged_phase": phase if verdict == "wedged" else "",
        "phases": [{"phase": "plugin_discovery", "seconds": 0.2,
                    "ok": True}],
        "samples": ([{"frame": frame, "count": count,
                      "stack": [frame, "backends (xla_bridge.py:50)"]}]
                    if verdict == "wedged" else []),
    }
    if verdict == "ok":
        rec["phases"] = [
            {"phase": p, "seconds": 1.0, "ok": True}
            for p in backend.PROBE_PHASES]
    return rec


def test_render_device_doctor_cross_session(tmp_path):
    records = [
        _record(100.0, "ok"),
        _record(200.0, "ok"),
        _record(300.0, "wedged"),
        _record(400.0, "wedged"),
        _record(500.0, "wedged", key="b" * 32),
    ]
    out = deviceprobe.render_device_doctor(records)
    assert "5 probe attempts" in out
    assert "ok×2" in out and "wedged×3" in out
    assert "dominant wedge: phase 'client_init' (3 of 3" in out
    assert "make_c_api_client" in out
    assert "via backends" in out
    assert "12 identical samples" in out
    assert "last healthy:" in out
    # The route regressed AFTER a healthy window — named explicitly.
    assert "SINCE the last healthy init" in out
    # Two attachments, histories kept apart.
    assert "aaaaaaaaaaaa…" in out and "bbbbbbbbbbbb…" in out
    assert "healthy-path phase p50" in out


def test_ledger_append_read_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv("MAKISU_TPU_DEVICE_SESSIONS_DIR",
                       str(tmp_path / "s"))
    rec = _record(1.0, "wedged")
    path = deviceprobe.append_record(rec)
    assert path is not None
    deviceprobe.append_record(_record(2.0, "ok"))
    records = deviceprobe.read_records()
    assert [r["verdict"] for r in records] == ["wedged", "ok"]
    # A file path works as well as the directory.
    assert len(deviceprobe.read_records(path)) == 2
    digest = deviceprobe.tail(limit=1)
    assert digest["records"] == 2
    assert digest["verdicts"] == {"ok": 1, "wedged": 1}
    assert digest["tail"][0]["verdict"] == "ok"


def test_ledger_disabled_is_noop(monkeypatch):
    monkeypatch.setenv("MAKISU_TPU_DEVICE_SESSIONS_DIR", "")
    assert deviceprobe.sessions_dir() is None
    assert deviceprobe.append_record(_record(1.0, "ok")) is None
    assert deviceprobe.read_records() == []


# -- flight-recorder integration -------------------------------------------


def test_bundle_carries_probe_and_doctor_renders_it(fresh_probe,
                                                    monkeypatch):
    release = threading.Event()
    monkeypatch.setenv("MAKISU_TPU_PROBE_SAMPLE_INTERVAL", "0.02")
    monkeypatch.setenv("MAKISU_TPU_PROBE_TIMEOUT", "0.3")
    monkeypatch.setattr(backend, "_phase_client_init",
                        _hanging_client_init(release))
    try:
        assert backend.backend_ready() is not None
        _wait_for(lambda: backend.probe_snapshot()["sample_count"] >= 1)
        from makisu_tpu.utils import flightrecorder
        recorder = flightrecorder.FlightRecorder()
        bundle = recorder.bundle("stall")
        probe = bundle["device_probe"]
        assert probe["state"] == "wedged"
        assert probe["phase"] == "client_init"
        rendered = flightrecorder.render_doctor(bundle)
        assert "device probe: wedged, in phase 'client_init'" in rendered
        assert "backend init wedged in probe phase" in rendered
    finally:
        _drain_probe_threads(release)


# -- device execution telemetry --------------------------------------------


def test_lane_batcher_exports_dispatch_telemetry(monkeypatch):
    """The XLA lane route (the device path's shape, runnable on the
    CPU backend) exports per-bucket dispatch latency, compile time,
    H2D bytes, and padding waste."""
    monkeypatch.setenv("MAKISU_TPU_CHUNK_NATIVE", "0")
    from makisu_tpu.chunker.cdc import ChunkSession
    g = metrics.global_registry()
    before_h2d = g.counter_total(metrics.DEVICE_H2D_BYTES)
    before_waste = g.counter_total(metrics.DEVICE_PADDING_WASTE)
    payload = np.random.default_rng(0).integers(
        0, 256, size=300_000, dtype=np.uint8).tobytes()
    s = ChunkSession(block=64 * 1024)
    s.update(payload)
    chunks = s.finish()
    assert chunks and not s._native
    assert g.counter_total(metrics.DEVICE_H2D_BYTES) > before_h2d
    # ~8KiB chunks in 16KiB lanes: padding waste is inevitable.
    assert g.counter_total(metrics.DEVICE_PADDING_WASTE) > before_waste
    assert g.gauge_value(metrics.DEVICE_COMPILE_SECONDS,
                         bucket=16 * 1024) > 0
    stats = backend.dispatch_stats()
    assert any(v.get("count", 0) >= 1 for v in stats.values())
    # /metrics carries the series (Prometheus text exposition).
    text = metrics.render_prometheus()
    assert "makisu_device_dispatch_seconds_bucket" in text
    assert "makisu_device_h2d_bytes_total" in text
    assert "makisu_device_padding_waste_bytes_total" in text
    assert "makisu_device_compile_seconds" in text
    # The healthz-facing aggregate.
    health = backend.device_health()
    assert health["h2d_bytes"] > 0
    assert health["padding_waste_bytes"] > 0
    assert health["probe"]["state"] in (
        "ok", "pending", "absent", "failed", "wedged", "disabled")
