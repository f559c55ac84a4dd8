"""Bounded, cached, phase-resolved JAX backend-readiness probe.

Backend init on an accelerator host can fail (no device, the chip held
by another process: one process owns a chip at a time) or block without
raising (a second process waiting on the first's libtpu lock). A hang
never raises, so without a guard the first ``gear_bitmap`` dispatch
would block a build forever.

``backend_ready()`` closes that gap: the first call runs the probe in
a daemon thread and waits a bounded time; callers on the device plane
consult it before their first dispatch and fail the build with the
probe's reason when the backend cannot come up
(``MAKISU_TPU_CHUNK_STRICT=0`` degrades the layer to whole-layer
caching instead; chunker/cdc.py "failure discipline"). The result is
cached process-wide, so a stuck init costs ONE bounded wait per
process — and if it eventually completes, later calls see the backend
as ready (the probe thread keeps running and flips the cached state).

What the probe learns, it keeps: platform, ``device_kind`` and device
count (:func:`device_identity`) ride on ``probe_snapshot()``, the
worker's ``/healthz`` ``device`` section and every ``--metrics-out``
report, so a result names the device it ran on.

Observability:

- The probe is PHASE-RESOLVED: the opaque ``jax.devices()`` wait is
  split into ``PROBE_PHASES`` (plugin discovery, PJRT client creation,
  device enumeration, first compile, first dispatch), each a
  ``metrics.span`` that also emits ``device_probe`` heartbeat events on
  the build event bus — so a stuck init names its phase.
- A sidecar WATCHER thread samples the probe thread's stack
  (``sys._current_frames``) on an interval: an init that parks inside a
  C call raises nothing, so the deepest-Python-frame trajectory ("12
  identical samples inside make_c_api_client via xla_bridge.backends")
  is the only diagnosis available. A C call that parks HOLDING the GIL
  freezes the watcher with everything else; only an outer timeout sees
  that one.
- Every probe attempt appends a ``makisu-tpu.deviceprobe.v1`` record
  (attachment fingerprint, per-phase timings, stack trajectory,
  verdict) to the device-session ledger (``utils/deviceprobe.py``),
  which ``makisu-tpu doctor --device`` renders across sessions.
- Once a backend is up, :func:`note_device_dispatch` aggregates the
  device execution plane per lane bucket: compile time (first
  dispatch), dispatch-latency rings, H2D bytes, and padding waste —
  exported via /metrics and the worker's ``/healthz`` ``device``
  section (:func:`device_health`).

The reference has no counterpart (its hashing is host-only,
lib/builder/step/common.go:35-67); this is accelerator-era failure
detection in the SURVEY §5 "failure recovery" sense.
"""

from __future__ import annotations

import collections
import contextlib
import os
import sys
import threading
import time

DEFAULT_TIMEOUT_SECONDS = 180.0

# Env prefixes that identify a device attachment (topology config).
# Shared signal: the device-session ledger fingerprints their values,
# and the worker's warm-probe gate checks their presence.
ATTACHMENT_ENV_PREFIXES = ("TPU_", "LIBTPU_")
# Attachment vars that are per-PROCESS, not per-attachment: they say
# nothing about which device a process is pointed at, so neither the
# ledger's fingerprint nor the warm-probe gate counts them.
ATTACHMENT_ENV_EXCLUDE = ("TPU_PROCESS_PORT", "TPU_WORKER_ID",
                          "TPU_VISIBLE_DEVICES")

_lock = threading.Lock()
_done = threading.Event()
_result: list = [None]  # [None] until the probe thread finishes;
#                         then ["ok"] or [error summary string]
_started = False
_probe_start = 0.0  # monotonic time the probe thread was started
_timed_out = False  # a full bounded wait already elapsed once
# What device enumeration found ({"platform", "device_kind",
# "device_count"}); None until the probe gets that far.
_identity: dict | None = None

# Probe sub-phases, in execution order. "client_init" is the PJRT
# C-API client creation, where a chip held by another process shows;
# the compile/dispatch phases exist because a backend that initializes
# can still fail its first program (distinct failure mode, distinct
# fix).
PROBE_PHASES = ("plugin_discovery", "client_init", "device_enumeration",
                "first_compile", "first_dispatch")

# Trajectory bound: consecutive identical deepest-frames collapse into
# one counted entry, so even an hours-long wedge stays a handful of
# entries; distinct-frame churn is trimmed from the front.
_SAMPLES_KEEP = 64
_SAMPLE_STACK_DEPTH = 12


class _ProbeTracker:
    """Phase + stack-sample state of this process's one probe attempt.
    Plain attribute stores and list appends only (GIL-atomic), so the
    forensics readers — /healthz, flight-recorder bundles from signal
    handlers — never need a lock the probe path might hold."""

    def __init__(self) -> None:
        self.source = "build"   # who started the probe (build|worker)
        self.phases: list[dict] = []   # [{"phase", "seconds", "ok"}]
        self.current = ""              # phase currently executing
        self.samples: list[dict] = []  # [{"frame", "count", "stack"}]
        self.last_beat = 0.0           # monotonic: last phase event/sample
        self.verdict = ""              # ""|ok|failed|wedged|ok_late|...
        self.detail = ""
        # Set once a terminal ledger record (or the wedge record) has
        # been appended — tests and CI smokes wait on this instead of
        # polling the filesystem.
        self.recorded = threading.Event()

    def phase_reached(self) -> str:
        """The last phase that COMPLETED ok ("" if none did)."""
        reached = ""
        for p in self.phases:
            if p.get("ok"):
                reached = p["phase"]
        return reached


_tracker = _ProbeTracker()


@contextlib.contextmanager
def _phase(name: str):
    """One probe sub-phase: a span on the global registry (visible in
    flight-recorder bundles as an open span while wedged) plus
    ``device_probe`` start/done heartbeat events on the event bus."""
    from makisu_tpu.utils import events, metrics
    tracker = _tracker
    tracker.current = name
    tracker.last_beat = time.monotonic()
    events.emit("device_probe", phase=name, status="start")
    t0 = time.monotonic()
    ok = False
    try:
        with metrics.span(f"device_probe.{name}"):
            yield
        ok = True
    finally:
        dt = time.monotonic() - t0
        tracker.phases.append({"phase": name,
                               "seconds": round(dt, 4), "ok": ok})
        tracker.current = ""
        tracker.last_beat = time.monotonic()
        events.emit("device_probe", phase=name,
                    status="done" if ok else "error",
                    seconds=round(dt, 4))


def _phase_plugin_discovery(ctx: dict) -> None:
    """Import jax and enumerate PJRT plugin entry points — the
    attachment-discovery work backend init will consume."""
    import jax
    ctx["jax"] = jax
    try:
        from importlib import metadata
        ctx["plugins"] = sorted(
            ep.name for ep in metadata.entry_points(group="jax_plugins"))
    except Exception:  # noqa: BLE001 - discovery listing is advisory
        ctx["plugins"] = []


def _phase_client_init(ctx: dict) -> None:
    """PJRT client creation (``make_c_api_client``): where a missing
    device raises and a chip held by another process fails or parks."""
    ctx["devices"] = ctx["jax"].devices()


def _read_identity(jax) -> dict:
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": str(devices[0].device_kind),
            "device_count": len(devices)}


def _annotate_spans(jax) -> None:
    """Put ``metrics.span`` on the profiler's clock: from here on every
    span also opens a ``TraceAnnotation`` on its thread, which costs a
    no-op native call unless a profiler session is running (``build
    --jax-profile``, the benchmark's traced run)."""
    from makisu_tpu.utils import metrics
    metrics.set_annotation_factory(jax.profiler.TraceAnnotation)


def _phase_device_enumeration(ctx: dict) -> None:
    global _identity
    _identity = _read_identity(ctx["jax"])
    _annotate_spans(ctx["jax"])


def _phase_first_compile(ctx: dict) -> None:
    """Compile one trivial program ahead of execution (AOT lower +
    compile) so a compile-service wedge is distinguishable from a
    dispatch wedge."""
    import jax.numpy as jnp
    jax = ctx["jax"]
    ctx["probe_arg"] = jnp.zeros((8,), jnp.uint8)
    ctx["compiled"] = jax.jit(
        lambda x: x + jnp.uint8(1)).lower(ctx["probe_arg"]).compile()


def _phase_first_dispatch(ctx: dict) -> None:
    """Execute the compiled program and block on the readback — the
    first full host→device→host round trip."""
    import numpy as np
    np.asarray(ctx["compiled"](ctx["probe_arg"]))


def _probe() -> None:
    ctx: dict = {}
    try:
        for name in PROBE_PHASES:
            # globals() lookup at run time: tests monkeypatch
            # individual phase functions to simulate wedges.
            fn = globals()["_phase_" + name]
            with _phase(name):
                fn(ctx)
        _result[0] = "ok"
    except Exception as e:  # noqa: BLE001 - init failures become a reason
        _result[0] = f"backend init failed: {e}"
    finally:
        _done.set()


def _sample_interval() -> float:
    """Seconds between probe-thread stack samples
    (MAKISU_TPU_PROBE_SAMPLE_INTERVAL, default 1s)."""
    try:
        return max(float(os.environ.get(
            "MAKISU_TPU_PROBE_SAMPLE_INTERVAL", "1.0")), 0.01)
    except ValueError:
        return 1.0


# Frames that are the interpreter's parking lot, not a location:
# Event/Condition waits. The REAL wedge parks inside a C call (no
# Python frame below the caller at all); simulated wedges park in
# threading waits — skipping these names the caller either way.
_PARKING_FILES = ("threading.py",)


def _representative_frame(stack: list[str]) -> str:
    for entry in stack:
        if not any(f"({name}:" in entry for name in _PARKING_FILES):
            return entry
    return stack[0]


def _sample_probe_stack(tracker: _ProbeTracker, ident) -> None:
    """One stack sample of the probe thread: record the deepest
    meaningful Python frame (innermost first); consecutive identical
    frames collapse into a counted entry — "N identical samples" IS
    the wedge signature."""
    if ident is None:
        return
    frame = sys._current_frames().get(ident)
    if frame is None:
        return
    stack: list[str] = []
    f = frame
    while f is not None and len(stack) < _SAMPLE_STACK_DEPTH:
        code = f.f_code
        stack.append(f"{code.co_name} "
                     f"({os.path.basename(code.co_filename)}:"
                     f"{f.f_lineno})")
        f = f.f_back
    if not stack:
        return
    deepest = _representative_frame(stack)
    samples = tracker.samples
    if samples and samples[-1]["frame"] == deepest:
        samples[-1]["count"] += 1
    else:
        if len(samples) >= _SAMPLES_KEEP:
            del samples[:_SAMPLES_KEEP // 4]
        samples.append({"frame": deepest, "count": 1, "stack": stack})
    tracker.last_beat = time.monotonic()


def _recording_wanted() -> bool:
    """Whether probe attempts should append to the device-session
    ledger. Explicit ``MAKISU_TPU_DEVICE_SESSIONS_DIR`` always decides
    (empty value = off); otherwise record exactly when a device is
    configured for this process — the same signal the warm-probe gate
    uses — so plain CPU test runs never litter the repo's ledger while
    every real device attempt (the data we need) is kept."""
    from makisu_tpu.utils import deviceprobe
    if os.environ.get("MAKISU_TPU_DEVICE_SESSIONS_DIR") is not None:
        return deviceprobe.sessions_dir() is not None
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms:
        return platforms.lower() != "cpu"
    return any(k.startswith(ATTACHMENT_ENV_PREFIXES)
               and k not in ATTACHMENT_ENV_EXCLUDE
               for k in os.environ)


def _attachment_key() -> str:
    """Fingerprint of the device attachment a ledger record belongs
    to: JAX_PLATFORMS plus every TPU_*/LIBTPU_* env var (where topology
    configuration lives), so ``doctor --device`` keeps two attachments'
    histories apart. Hashed before it leaves the process: the raw
    values must not land in a shared artifact."""
    import hashlib
    parts = [os.environ.get("JAX_PLATFORMS", "(default)")]
    parts += sorted(
        f"{k}={v}" for k, v in os.environ.items()
        if k.startswith(ATTACHMENT_ENV_PREFIXES)
        and k not in ATTACHMENT_ENV_EXCLUDE)
    return hashlib.sha256("|".join(parts).encode()).hexdigest()[:32]


def _record_attempt(tracker: _ProbeTracker, verdict: str, detail: str,
                    timeout: float, probe_start: float) -> None:
    """Append one ``makisu-tpu.deviceprobe.v1`` record for this probe
    attempt. Never raises — the ledger is forensics, not control
    flow."""
    try:
        if not _recording_wanted():
            return
        from makisu_tpu.utils import deviceprobe
        record = {
            "schema": deviceprobe.SCHEMA,
            "ts": round(time.time(), 3),
            "pid": os.getpid(),
            "source": tracker.source,
            "platform": os.environ.get("JAX_PLATFORMS", "") or
                        "(default)",
            "attachment": {
                # Hashed key (raw endpoint values must not land in a
                # shared artifact) + the var NAMES present, so a reader
                # can tell two attachments apart and knows what to dump.
                "key": _attachment_key(),
                "vars": sorted(
                    k for k in os.environ
                    if k.startswith(ATTACHMENT_ENV_PREFIXES)
                    and k not in ATTACHMENT_ENV_EXCLUDE),
            },
            "verdict": verdict,
            "detail": (detail or "")[:300],
            "timeout_seconds": round(timeout, 1),
            "total_seconds": round(time.monotonic() - probe_start, 3),
            "phase_reached": tracker.phase_reached(),
            "wedged_phase": (tracker.current
                             if verdict == "wedged" else ""),
            "phases": [dict(p) for p in tracker.phases],
            "samples": [dict(s) for s in tracker.samples],
        }
        if deviceprobe.append_record(record) is not None:
            tracker.recorded.set()
    except Exception:  # noqa: BLE001 - ledger must never fail the probe
        pass


def _watch(probe_thread: threading.Thread, timeout: float,
           done: threading.Event, tracker: _ProbeTracker,
           probe_start: float) -> None:
    """Sidecar watcher: samples the probe thread's stack on an
    interval; when the bounded budget elapses without completion it
    appends the WEDGED ledger record (phase + trajectory — the
    diagnosis no exception path can produce, because the wedge parks
    inside a C call), then keeps sampling so a late completion still
    leaves an ``ok_late``/``failed_late`` record."""
    from makisu_tpu.utils import events
    # This thread's own activity must not stamp the build-progress
    # clock it would otherwise keep fresh through a genuine wedge.
    events.suppress_progress_stamps()
    interval = _sample_interval()
    wedge_written = False
    while not done.wait(interval):
        try:
            _sample_probe_stack(tracker, probe_thread.ident)
            elapsed = time.monotonic() - probe_start
            if not wedge_written and timeout > 0 and elapsed >= timeout:
                wedge_written = True
                tracker.verdict = "wedged"
                tracker.detail = (
                    f"backend init did not complete within "
                    f"{timeout:.0f}s (wedged in "
                    f"{tracker.current or '?'})")
                _record_attempt(tracker, "wedged", tracker.detail,
                                timeout, probe_start)
                events.emit("device_probe", status="wedged",
                            phase=tracker.current,
                            elapsed=round(elapsed, 1))
        except Exception:  # noqa: BLE001 - watcher must never die early
            pass
    verdict = "ok" if _result[0] == "ok" else "failed"
    if wedge_written:
        verdict += "_late"
    tracker.verdict = verdict
    tracker.detail = "" if _result[0] == "ok" else str(_result[0] or "")
    _record_attempt(tracker, verdict, tracker.detail, timeout,
                    probe_start)
    tracker.recorded.set()  # terminal — even when recording is gated off


def wait_for_probe_record(timeout: float = 5.0) -> bool:
    """Block until this process's probe attempt has reached a recorded
    verdict (ledger appended, or recording gated off after
    completion). CI smokes and tests use this instead of polling."""
    return _tracker.recorded.wait(timeout)


def _reset_probe_state_for_tests() -> None:
    """Fresh probe state (tests only): the module caches one probe per
    process by design."""
    global _done, _result, _started, _probe_start, _timed_out, \
        _identity, _tracker
    _done = threading.Event()
    _result = [None]
    _started = False
    _probe_start = 0.0
    _timed_out = False
    _identity = None
    _tracker = _ProbeTracker()


def init_timeout() -> float:
    """Seconds to wait for backend init (MAKISU_TPU_PROBE_TIMEOUT, with
    MAKISU_TPU_BACKEND_INIT_TIMEOUT as the original alias; 0 disables
    the guard entirely — callers then block natively)."""
    for var in ("MAKISU_TPU_PROBE_TIMEOUT",
                "MAKISU_TPU_BACKEND_INIT_TIMEOUT"):
        if os.environ.get(var):
            return float(os.environ[var])
    return DEFAULT_TIMEOUT_SECONDS


def sync_timeout() -> float:
    """Seconds to wait for a device→host readback
    (MAKISU_TPU_SYNC_TIMEOUT; 0 disables the guard)."""
    return float(os.environ.get("MAKISU_TPU_SYNC_TIMEOUT", "300"))


def sync_bounded(x, what: str, timeout: float | None = None):
    """``np.asarray(x)`` with a bounded wait.

    Backend init is not the only place a device can stop answering: a
    backend that initialized fine can hang mid-build at the readback
    sync point instead — which no exception discipline catches. This
    runs the readback in a daemon thread and raises ``TimeoutError``
    after ``timeout`` seconds (default: ``sync_timeout()``), turning
    the hang into a normal device-plane error that fails the build
    with its reason. The abandoned thread stays parked in the plugin;
    acceptable for a daemon.
    """
    import numpy as np

    from makisu_tpu.utils import metrics

    if timeout is None:
        timeout = sync_timeout()
    if timeout <= 0:
        return np.asarray(x)
    result: dict = {}

    def run() -> None:
        try:
            result["v"] = np.asarray(x)
        except BaseException as e:  # noqa: BLE001 - re-raised below
            result["e"] = e

    t = threading.Thread(target=run, daemon=True,
                         name="device-readback")
    t0 = time.monotonic()
    t.start()
    t.join(timeout)
    metrics.observe("makisu_device_sync_seconds",
                    time.monotonic() - t0)
    if t.is_alive():
        metrics.counter_add("makisu_device_sync_total", result="timeout")
        raise TimeoutError(
            f"{what} did not complete within {timeout:.0f}s "
            "(device stopped answering mid-build?)")
    if "e" in result:
        metrics.counter_add("makisu_device_sync_total", result="error")
        raise result["e"]
    metrics.counter_add("makisu_device_sync_total", result="ok")
    return result["v"]


def backend_ready(timeout: float | None = None,
                  source: str = "build") -> str | None:
    """Block (bounded) until the default JAX backend is initialized.

    Returns None when the backend is ready, else a failure summary.
    The wait is ``timeout`` seconds from PROBE START (default:
    ``init_timeout()``) — so a process that warmed the probe early (the
    worker does at startup) pays only the remainder, usually nothing,
    when the first build consults it. A timeout cannot cancel the
    underlying init — the daemon thread stays parked in the plugin —
    but the caller gets control back, and every later call re-checks
    instantly (and picks up a late success).

    ``source`` labels the deviceprobe ledger record when THIS call is
    the one that starts the probe (build|worker).
    """
    global _timed_out, _identity
    if timeout is None:
        timeout = init_timeout()
    if timeout <= 0:
        # Guard disabled: block natively, here, so that "ready" always
        # means the identity is known.
        if _identity is None:
            import jax
            _identity = _read_identity(jax)
            _annotate_spans(jax)
        return None
    warm_probe(source=source)
    if _done.is_set():
        return None if _result[0] == "ok" else _result[0]
    if _timed_out:
        # One full bounded wait already elapsed in this process; don't
        # charge it again per layer/session — report wedged instantly
        # (a late init completion flips _done and is picked up above).
        return "backend init still pending (device held or absent?)"
    remaining = (_probe_start + timeout) - time.monotonic()
    if remaining > 0 and _done.wait(remaining):
        return None if _result[0] == "ok" else _result[0]
    _timed_out = True
    return (f"backend init did not complete within {timeout:.0f}s "
            "(device held or absent?)")


def warm_probe(source: str = "build") -> None:
    """Start the background readiness probe without waiting (worker
    startup; also the first step of every ``backend_ready`` call): by
    the time the first build's ChunkSession consults
    ``backend_ready()``, a healthy backend has usually finished
    initializing and a stuck one charges the build only the remainder
    of the budget — not a fresh full wait.

    Alongside the probe thread a watcher thread starts: stack samples
    on an interval, the wedged-verdict ledger record at budget expiry,
    the terminal record on completion (see :func:`_watch`)."""
    global _started, _probe_start
    with _lock:
        if not _started:
            _started = True
            _tracker.source = source
            _probe_start = time.monotonic()
            t = threading.Thread(target=_probe, daemon=True,
                                 name="jax-backend-probe")
            t.start()
            threading.Thread(
                target=_watch,
                args=(t, init_timeout(), _done, _tracker, _probe_start),
                daemon=True, name="jax-probe-watch").start()


# -- probe introspection (healthz, history, forensics) ---------------------


def probe_snapshot() -> dict:
    """JSON-ready state of this process's probe attempt. Lock-free by
    construction (tracker fields are GIL-atomic stores), so the flight
    recorder can call it from a signal handler.

    ``state``: ``disabled`` (guard off) | ``absent`` (never started) |
    ``pending`` | ``ok`` | ``failed`` | ``wedged`` (budget elapsed,
    still parked)."""
    from makisu_tpu.utils import metrics
    timeout = init_timeout()
    if timeout <= 0:
        state = "disabled"
    elif not _started:
        state = "absent"
    elif _done.is_set():
        state = "ok" if _result[0] == "ok" else "failed"
    elif time.monotonic() - _probe_start >= timeout:
        state = "wedged"
    else:
        state = "pending"
    tracker = _tracker
    samples = [dict(s) for s in
               metrics.snapshot_concurrent(tracker.samples)]
    out: dict = {
        "state": state,
        "phase": tracker.current,
        "phase_reached": tracker.phase_reached(),
        "phases": [dict(p) for p in
                   metrics.snapshot_concurrent(tracker.phases)],
        "samples": samples,
        "sample_count": sum(int(s.get("count", 0)) for s in samples),
    }
    if _identity is not None:
        out.update(_identity)
    if _started:
        out["source"] = tracker.source
        out["elapsed_seconds"] = round(
            time.monotonic() - _probe_start, 3)
        if tracker.last_beat:
            out["heartbeat_age_seconds"] = round(
                time.monotonic() - tracker.last_beat, 3)
    if samples:
        out["deepest_frame"] = samples[-1]["frame"]
    detail = tracker.detail or (
        _result[0] if _done.is_set() and _result[0] != "ok" else "")
    if detail:
        out["detail"] = str(detail)[:300]
    return out


def device_identity() -> dict | None:
    """``{"platform", "device_kind", "device_count"}`` as JAX reported
    them to this process's probe, or None while no backend is up."""
    return dict(_identity) if _identity is not None else None


def probe_label() -> str:
    """One-word device-route label for history records
    (``utils/history.py``): ``ok`` | ``wedged`` | ``failed`` |
    ``pending`` | ``absent`` | ``disabled``."""
    return probe_snapshot()["state"]


# -- device execution telemetry --------------------------------------------
#
# Once a backend IS up, the questions change: how long did each bucket's
# program take to compile, what does a dispatch round trip cost, how
# many bytes cross to the device per program, and how much of each
# padded lane buffer is waste (the padding the ragged-batch work —
# ROADMAP item 3, arxiv 2604.15464 — exists to remove). One helper
# aggregates all of it so the HashService and the lane batcher can't
# drift apart.

_DISPATCH_RING_KEEP = 256

_dispatch_lock = threading.Lock()
_dispatch_rings: dict[int, "collections.deque[float]"] = {}
_compiled_buckets: set[int] = set()


def note_device_dispatch(bucket: int, lanes: int, filled: int,
                         real_bytes: int, seconds: float) -> None:
    """Record one dispatched device program for lane bucket ``bucket``
    (its byte capacity): ``lanes`` total lanes shipped, ``filled`` of
    them carrying real chunks totalling ``real_bytes``, the round trip
    taking ``seconds`` (dispatch → readback complete).

    Exports, per bucket: ``makisu_device_dispatch_seconds`` histogram,
    ``makisu_device_compile_seconds`` gauge (the first dispatch of a
    bucket's program pays its XLA compile; later dispatches reuse it),
    ``makisu_device_h2d_bytes_total`` (the full padded buffer ships),
    and ``makisu_device_padding_waste_bytes_total`` (padded−real bytes
    across the FILLED lanes — empty lanes are the occupancy
    histogram's story). A bounded per-bucket latency ring backs the
    exact p50/p99 the ``/healthz`` ``device`` section serves."""
    from makisu_tpu.utils import metrics
    with _dispatch_lock:
        ring = _dispatch_rings.get(bucket)
        if ring is None:
            ring = _dispatch_rings[bucket] = collections.deque(
                maxlen=_DISPATCH_RING_KEEP)
        first = bucket not in _compiled_buckets
        if first:
            _compiled_buckets.add(bucket)
        ring.append(seconds)
    if first:
        metrics.gauge_set(metrics.DEVICE_COMPILE_SECONDS, seconds,
                          bucket=bucket)
    metrics.observe(metrics.DEVICE_DISPATCH_SECONDS, seconds,
                    bucket=bucket)
    metrics.counter_add(metrics.DEVICE_H2D_BYTES, lanes * bucket,
                        bucket=bucket)
    metrics.counter_add(metrics.DEVICE_PADDING_WASTE,
                        max(filled * bucket - real_bytes, 0),
                        bucket=bucket)


def dispatch_stats() -> dict:
    """Exact per-bucket dispatch-latency percentiles over the recent
    ring (the ``/healthz`` device section's latency digest)."""
    from makisu_tpu.utils import metrics
    with _dispatch_lock:
        rings = {b: list(r) for b, r in _dispatch_rings.items()}
    return {str(b): metrics.percentile_stats(v)
            for b, v in sorted(rings.items())}


def device_health() -> dict:
    """The worker ``/healthz`` ``device`` section: probe state (device
    identity, phase, heartbeat age, deepest sampled frame) + the
    execution plane's per-bucket dispatch digests, byte totals and
    shared-service batch counts."""
    from makisu_tpu.utils import metrics
    snap = probe_snapshot()
    probe = {"state": snap["state"]}
    for key in ("platform", "device_kind", "device_count", "phase",
                "phase_reached", "sample_count", "source",
                "elapsed_seconds", "heartbeat_age_seconds",
                "deepest_frame", "detail"):
        if snap.get(key) not in (None, "", 0) or key == "sample_count":
            probe[key] = snap.get(key)
    g = metrics.global_registry()
    return {
        "probe": probe,
        "dispatch_seconds": dispatch_stats(),
        "h2d_bytes": int(g.counter_total(metrics.DEVICE_H2D_BYTES)),
        "padding_waste_bytes": int(
            g.counter_total(metrics.DEVICE_PADDING_WASTE)),
        # The shared HashService's programs (chunker/service.py): how
        # many ran, how many failed, how many mixed several builds.
        "hash_batches": int(g.counter_total("makisu_hash_batches_total")),
        "hash_batch_failures": int(
            g.counter_total("makisu_hash_batch_failures_total")),
        "hash_cross_build_batches": int(
            g.counter_total("makisu_hash_cross_build_batches_total")),
    }
