"""Failure forensics: flight-recorder bundles, the stall watchdog,
`makisu-tpu doctor`, and mid-flight `makisu-tpu report`.

The central scenario: a deliberately-wedged build must leave a
diagnostic bundle whose stuck span, thread stacks, and `stall` event
match a golden shape, and the doctor/report subcommands must turn that
bundle into a correct diagnosis."""

import contextlib
import json
import os
import signal
import subprocess
import sys
import threading
import time

import pytest

from makisu_tpu import cli
from makisu_tpu.utils import events, flightrecorder, metrics, resources
from makisu_tpu.utils import logging as log

BUNDLE_KEYS = {"schema", "reason", "ts", "build", "last_progress_seconds",
               "events", "logs", "open_spans", "threads", "transfer",
               "resources", "metrics"}


def _wedged_transfer_wait(release: threading.Event) -> None:
    """Stands in for a transfer thread stuck on a dead registry; the
    bundle's thread stacks must name this frame."""
    release.wait(timeout=30)


@pytest.fixture
def wedged_bundle(tmp_path):
    """Run the wedged-fake-build scenario once: a build with an open
    span chain (one completed child), a wedged worker thread, and a
    stall watchdog with a tiny window. Yields (bundle dict, path)."""
    bundle_path = str(tmp_path / "bundle.json")
    registry = metrics.MetricsRegistry()
    reg_token = metrics.set_build_registry(registry)
    recorder = flightrecorder.FlightRecorder()
    tokens = flightrecorder.install(recorder)
    release = threading.Event()
    wedged = threading.Thread(target=_wedged_transfer_wait,
                              args=(release,), name="transfer-blob-w0")
    wedged.start()
    watchdog = None
    try:
        with contextlib.ExitStack() as stack:
            stack.enter_context(metrics.span("build"))
            with metrics.span("commit_layer"):  # a COMPLETED span
                time.sleep(0.02)
            stack.enter_context(metrics.span("step", directive="RUN"))
            log.info("about to wedge the fake build")
            watchdog = flightrecorder.StallWatchdog(
                0.3, recorder, bundle_path, registry).start()
            deadline = time.monotonic() + 10.0
            while (not os.path.exists(bundle_path)
                   and time.monotonic() < deadline):
                time.sleep(0.05)
    finally:
        if watchdog is not None:
            watchdog.stop()
        release.set()
        wedged.join(timeout=5)
        flightrecorder.uninstall(tokens)
        metrics.reset_build_registry(reg_token)
    assert os.path.exists(bundle_path), "watchdog never dumped a bundle"
    with open(bundle_path, encoding="utf-8") as f:
        return json.load(f), bundle_path


def test_wedged_build_bundle_golden_shape(wedged_bundle):
    bundle, _path = wedged_bundle
    # Golden shape: every section present, schema/reason right.
    assert bundle["schema"] == "makisu-tpu.flightrecorder.v1"
    assert bundle["reason"] == "stall"
    assert BUNDLE_KEYS <= set(bundle)
    assert bundle["last_progress_seconds"] >= 0.3

    # The stuck span chain: build -> step, step is the open LEAF with
    # an age at least the watchdog window; commit_layer closed and so
    # must NOT appear.
    open_names = {s["name"] for s in bundle["open_spans"]}
    assert {"build", "step"} <= open_names
    assert "commit_layer" not in open_names
    step = next(s for s in bundle["open_spans"] if s["name"] == "step")
    build = next(s for s in bundle["open_spans"] if s["name"] == "build")
    assert step["leaf"] and not build["leaf"]
    assert step["age_seconds"] >= 0.3
    assert step["attrs"] == {"directive": "RUN"}
    assert step["parent_id"] == build["span_id"]

    # The stall event was fired into the build's own sinks and is the
    # ring's last event (span/log records precede it).
    stall_events = [e for e in bundle["events"] if e["type"] == "stall"]
    assert len(stall_events) == 1
    assert stall_events[0]["idle_seconds"] >= 0.3
    assert stall_events[0]["window_seconds"] == 0.3
    assert bundle["events"][-1]["type"] == "stall"
    assert any(e["type"] == "span_start" for e in bundle["events"])

    # All-thread stacks name the wedged thread and its stuck frame.
    by_name = {t["name"]: t for t in bundle["threads"]}
    assert "transfer-blob-w0" in by_name
    assert any("_wedged_transfer_wait" in frame
               for frame in by_name["transfer-blob-w0"]["stack"])
    assert "MainThread" in by_name

    # Log ring captured the pre-wedge record; metrics snapshot is the
    # build registry's (trace ids match).
    assert any("about to wedge" in r["msg"] for r in bundle["logs"])
    assert bundle["metrics"]["schema"] == "makisu-tpu.metrics.v1"
    assert bundle["metrics"]["trace_id"] == bundle["build"]["trace_id"]


def test_doctor_renders_diagnosis(wedged_bundle, capsys):
    bundle, path = wedged_bundle
    # The doctor lists a bundle's first 16 threads, and late in a long
    # session other tests' pools linger in this process: render the
    # fake build's own two.
    bundle["threads"] = [t for t in bundle["threads"]
                         if t["name"] in ("MainThread", "transfer-blob-w0")]
    text = flightrecorder.render_doctor(bundle)
    assert "reason: stall" in text
    assert "stuck" in text and "'step'" in text  # the stuck leaf span
    assert "transfer-blob-w0" in text            # the wedged thread
    assert "stall" in text                       # the event tail
    # Round-trip through the CLI subcommand.
    assert cli.main(["doctor", path]) == 0
    out = capsys.readouterr().out
    assert "makisu-tpu doctor" in out
    assert "'step'" in out


def test_doctor_rejects_non_bundle(tmp_path):
    bogus = tmp_path / "not-a-bundle.json"
    bogus.write_text('{"hello": "world"}')
    with pytest.raises(SystemExit, match="not a makisu-tpu diagnostic"):
        cli.main(["doctor", str(bogus)])


def test_report_on_bundle_marks_open_spans(wedged_bundle, capsys):
    """`makisu-tpu report` pointed at a bundle of a build that died
    mid-flight: completed spans still get phase self-times; open ones
    are listed and marked."""
    _bundle, path = wedged_bundle
    assert cli.main(["report", path]) == 0
    out = capsys.readouterr().out
    assert "build died mid-flight" in out
    assert "spans still open at capture" in out
    assert "✱ open" in out
    assert "step" in out
    # The completed commit_layer span contributes hash-phase self time.
    assert "commit_layer" in out
    hash_part = out.split("hash=")[1]
    assert float(hash_part.split("s")[0]) > 0


def test_watchdog_does_not_fire_while_progressing(tmp_path):
    bundle_path = tmp_path / "no-bundle.json"
    recorder = flightrecorder.FlightRecorder()
    watchdog = flightrecorder.StallWatchdog(
        0.5, recorder, str(bundle_path)).start()
    try:
        for _ in range(12):
            events.emit("step", phase="tick")
            time.sleep(0.07)
    finally:
        watchdog.stop()
    assert not bundle_path.exists()
    assert not recorder.dumped


def test_permanent_wedge_fires_once_and_clock_climbs(tmp_path):
    """The watchdog's own stall emit and warning log must not count as
    progress: a permanent wedge produces exactly ONE stall event, and
    the progress clock (what /healthz reports) keeps climbing past the
    window instead of being reset by the forensics."""
    bundle_path = str(tmp_path / "once.json")
    recorder = flightrecorder.FlightRecorder()
    tokens = flightrecorder.install(recorder)
    watchdog = None
    try:
        events.emit("last_real_progress")
        watchdog = flightrecorder.StallWatchdog(
            0.2, recorder, bundle_path).start()
        time.sleep(1.0)
        stalls = [e for e in recorder._snapshot(recorder._events)
                  if e["type"] == "stall"]
        assert len(stalls) == 1
        assert flightrecorder.last_progress_seconds() >= 0.8
    finally:
        if watchdog is not None:
            watchdog.stop()
        flightrecorder.uninstall(tokens)


def test_per_build_bundle_excludes_other_builds_spans():
    """A per-build bundle filters the process-wide open-span set to
    its own trace — in a worker, build B's bundle must not blame a
    healthy build A's long-running span."""
    reg_a = metrics.MetricsRegistry()
    reg_b = metrics.MetricsRegistry()
    recorder = flightrecorder.FlightRecorder()
    token_a = metrics.set_build_registry(reg_a)
    try:
        with metrics.span("build_a_stage"):
            token_b = metrics.set_build_registry(reg_b)
            try:
                with metrics.span("build_b_step"):
                    bundle_b = recorder.bundle("failure", reg_b)
                    process_bundle = recorder.bundle(
                        "inspect", metrics.global_registry())
            finally:
                metrics.reset_build_registry(token_b)
    finally:
        metrics.reset_build_registry(token_a)
    names_b = {s["name"] for s in bundle_b["open_spans"]}
    assert names_b == {"build_b_step"}
    # The process-level view (worker SIGTERM bundle) keeps everything.
    names_all = {s["name"] for s in process_bundle["open_spans"]}
    assert {"build_a_stage", "build_b_step"} <= names_all


def test_per_build_watchdog_not_masked_by_sibling_progress(tmp_path):
    """A per-build watchdog watches ITS build's progress cell: a
    healthy sibling build stamping the process clock (bare thread, no
    cell) must not mask the wedged build's stall."""
    bundle_path = tmp_path / "masked.json"
    recorder = flightrecorder.FlightRecorder()
    cell_token = events.bind_progress_cell()
    stop_sibling = threading.Event()

    def sibling():
        # No progress cell in this thread's context: stamps only the
        # process-wide clock, like another build would.
        while not stop_sibling.wait(0.05):
            events.emit("sibling_step")

    noisy = threading.Thread(target=sibling)
    noisy.start()
    watchdog = None
    try:
        events.note_progress()  # the wedged build's last activity
        watchdog = flightrecorder.StallWatchdog(
            0.3, recorder, str(bundle_path),
            cell=events.progress_cell()).start()
        deadline = time.monotonic() + 10
        while (not bundle_path.exists()
               and time.monotonic() < deadline):
            time.sleep(0.05)
    finally:
        if watchdog is not None:
            watchdog.stop()
        stop_sibling.set()
        noisy.join(timeout=5)
        events.reset_progress_cell(cell_token)
    assert bundle_path.exists(), \
        "sibling progress masked the per-build watchdog"
    assert json.loads(bundle_path.read_text())["reason"] == "stall"


def test_watchdog_respects_active_fn(tmp_path):
    """An idle worker (active_fn False) must never read as stalled,
    no matter how long nothing happens."""
    bundle_path = tmp_path / "idle-bundle.json"
    recorder = flightrecorder.FlightRecorder()
    watchdog = flightrecorder.StallWatchdog(
        0.2, recorder, str(bundle_path), active_fn=lambda: False).start()
    try:
        time.sleep(0.6)
    finally:
        watchdog.stop()
    assert not bundle_path.exists()


def test_sigusr1_dump_does_not_suppress_failure_dump(tmp_path):
    """A SIGUSR1 inspection poke is not a terminal capture: the build's
    eventual failure bundle must still be written. Only stall/SIGTERM
    dumps — which froze the interesting moment — suppress it."""
    recorder = flightrecorder.FlightRecorder()
    recorder.dump(str(tmp_path / "poke.json"), "SIGUSR1")
    assert recorder.dumped
    assert not recorder.captured_terminal_moment()
    recorder.dump(str(tmp_path / "stall.json"), "stall")
    assert recorder.captured_terminal_moment()


def test_worker_watchdog_binds_process_registry(tmp_path):
    """The worker's stall watchdog must bundle against the GLOBAL
    registry even though the server is constructed inside cli.main's
    per-invocation context (whose trace filter would drop every
    build's open spans)."""
    from makisu_tpu.worker import WorkerServer

    build_registry = metrics.MetricsRegistry()
    token = metrics.set_build_registry(build_registry)  # as cli.main does
    try:
        server = WorkerServer(str(tmp_path / "wd.sock"),
                              stall_window=30.0)
        try:
            assert server._watchdog is not None
            assert server._watchdog.registry is metrics.global_registry()
        finally:
            server.server_close()
    finally:
        metrics.reset_build_registry(token)


def test_failure_dump_via_diag_out(tmp_path, capsys):
    """A plain failing build with --diag-out leaves a bundle with
    reason=failure and the exit code."""
    bundle_path = tmp_path / "fail-bundle.json"
    code = cli.main(["--diag-out", str(bundle_path),
                     "build", str(tmp_path / "nonexistent-ctx"),
                     "-t", "x:y",
                     "--storage", str(tmp_path / "storage"),
                     "--root", str(tmp_path / "root")])
    assert code == 1
    bundle = json.loads(bundle_path.read_text())
    assert bundle["reason"] == "failure"
    assert bundle["exit_code"] == 1
    assert bundle["schema"] == "makisu-tpu.flightrecorder.v1"
    # The ring captured the build lifecycle events.
    types = [e["type"] for e in bundle["events"]]
    assert "build_start" in types and "build_end" in types


def test_no_dump_without_opt_in(tmp_path, monkeypatch):
    """Without --diag-out or $MAKISU_TPU_DIAG_DIR a failing build
    writes no bundle (tests and ad-hoc runs must not litter /tmp)."""
    monkeypatch.delenv("MAKISU_TPU_DIAG_DIR", raising=False)
    before = set(os.listdir(tmp_path))
    code = cli.main(["build", str(tmp_path / "nope"), "-t", "x:y",
                     "--storage", str(tmp_path / "s"),
                     "--root", str(tmp_path / "r")])
    assert code == 1
    assert set(os.listdir(tmp_path)) == before


def test_failure_dump_via_diag_dir_env(tmp_path, monkeypatch):
    diag_dir = tmp_path / "diag"
    monkeypatch.setenv("MAKISU_TPU_DIAG_DIR", str(diag_dir))
    code = cli.main(["build", str(tmp_path / "nope"), "-t", "x:y",
                     "--storage", str(tmp_path / "s"),
                     "--root", str(tmp_path / "r")])
    assert code == 1
    bundles = list(diag_dir.glob("makisu-tpu-diag-*-failure.json"))
    assert len(bundles) == 1
    assert json.loads(bundles[0].read_text())["reason"] == "failure"


def _serve_wedge_image(reg):
    """Publish a one-layer image on a miniregistry whose every request
    sleeps: a FROM pull against it wedges a real build."""
    import gzip
    import io
    import tarfile

    from makisu_tpu.docker.image import (
        MEDIA_TYPE_CONFIG,
        MEDIA_TYPE_LAYER,
        Descriptor,
        Digest,
        DistributionManifest,
        ImageConfig,
    )

    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w|") as tw:
        info = tarfile.TarInfo("base.txt")
        payload = b"wedge" * 64
        info.size = len(payload)
        tw.addfile(info, io.BytesIO(payload))
    layer = gzip.compress(buf.getvalue(), mtime=0)
    config = ImageConfig()
    config.rootfs.diff_ids = [
        str(Digest.of_bytes(gzip.decompress(layer)))]
    config_blob = config.to_bytes()
    manifest = DistributionManifest(
        config=Descriptor(MEDIA_TYPE_CONFIG, len(config_blob),
                          Digest.of_bytes(config_blob)),
        layers=[Descriptor(MEDIA_TYPE_LAYER, len(layer),
                           Digest.of_bytes(layer))])
    repo = reg.state.repo("wedge/base")
    repo.blobs[str(Digest.of_bytes(config_blob))] = config_blob
    repo.blobs[str(Digest.of_bytes(layer))] = layer
    raw = manifest.to_bytes()
    media = "application/vnd.docker.distribution.manifest.v2+json"
    repo.manifests["1"] = (media, raw)
    repo.manifests[str(Digest.of_bytes(raw))] = (media, raw)
    repo.tags.add("1")


def test_sigterm_leaves_bundle(tmp_path):
    """Acceptance: a real build (subprocess) wedged pulling FROM a
    stalled registry and killed by SIGTERM leaves a bundle on disk
    that names the open span chain and the thread stacks."""
    from makisu_tpu.tools.miniregistry import MiniRegistry

    ctx = tmp_path / "ctx"
    ctx.mkdir()
    (tmp_path / "root").mkdir()
    bundle_path = tmp_path / "sigterm-bundle.json"
    with MiniRegistry(latency_s=30.0) as reg:
        _serve_wedge_image(reg)
        (ctx / "Dockerfile").write_text(
            f"FROM {reg.addr}/wedge/base:1\n")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("MAKISU_TPU_DIAG_DIR", None)
        proc = subprocess.Popen(
            [sys.executable, "-c",
             "import sys\n"
             "from makisu_tpu import cli\n"
             "sys.exit(cli.main(sys.argv[1:]))",
             "--diag-out", str(bundle_path),
             "build", str(ctx), "-t", "wedge/app:1",
             "--storage", str(tmp_path / "storage"),
             "--root", str(tmp_path / "root")],
            env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL)
        try:
            # Wait until the build is provably wedged inside the
            # registry's latency sleep (its first request arrived).
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                if reg.state.requests:
                    break
                if proc.poll() is not None:
                    pytest.fail("build exited before wedging")
                time.sleep(0.1)
            assert reg.state.requests, "build never reached the registry"
            time.sleep(0.3)  # let it sink into the blocking read
            proc.send_signal(signal.SIGTERM)
            code = proc.wait(timeout=60)
        finally:
            proc.kill()
    assert code == 128 + signal.SIGTERM
    bundle = json.loads(bundle_path.read_text())
    assert bundle["reason"] == "SIGTERM"
    # The open span chain reaches into the build; stacks captured.
    assert bundle["open_spans"], "no open spans in SIGTERM bundle"
    assert {"build"} <= {s["name"] for s in bundle["open_spans"]}
    assert any(t["name"] == "MainThread" for t in bundle["threads"])
    text = flightrecorder.render_doctor(bundle)
    assert "SIGTERM" in text


def test_worker_sigterm_leaves_process_bundle(tmp_path):
    """A worker killed by SIGTERM dumps ONE process-level bundle to
    --diag-out (reason SIGTERM, with the builds' events) — and the
    worker invocation's own exit path must not clobber it with an
    empty per-invocation failure bundle."""
    from makisu_tpu.worker import WorkerClient

    ctx = tmp_path / "ctx"
    ctx.mkdir()
    (ctx / "Dockerfile").write_text("FROM scratch\nCOPY f /f\n")
    (ctx / "f").write_text("x")
    (tmp_path / "root").mkdir()
    bundle_path = tmp_path / "worker-bundle.json"
    sock = str(tmp_path / "worker.sock")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m", "makisu_tpu.cli",
         "--diag-out", str(bundle_path), "worker", "--socket", sock],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    try:
        client = WorkerClient(sock)
        deadline = time.monotonic() + 120
        while not client.ready() and time.monotonic() < deadline:
            time.sleep(0.1)
        assert client.ready()
        assert client.build(["build", str(ctx), "-t", "wt/app:1",
                             "--storage", str(tmp_path / "storage"),
                             "--root", str(tmp_path / "root")]) == 0
        proc.send_signal(signal.SIGTERM)
        code = proc.wait(timeout=60)
    finally:
        proc.kill()
    assert code == 128 + signal.SIGTERM
    bundle = json.loads(bundle_path.read_text())
    assert bundle["reason"] == "SIGTERM"
    # Process-level view: the build's events are in the ring even
    # though the build ran in a handler thread's own context.
    assert any(e["type"] == "build_start" for e in bundle["events"])


def test_sigusr1_dumps_and_continues(tmp_path):
    """SIGUSR1 is the live-inspection signal: bundle written
    mid-build, build keeps running to a normal exit. The kick fires
    from an event sink on the first `step` event, so the signal
    provably lands while the build is inside its span tree."""
    ctx = tmp_path / "ctx"
    ctx.mkdir()
    (ctx / "Dockerfile").write_text("FROM scratch\nCOPY d.txt /d.txt\n")
    (ctx / "d.txt").write_text("payload")
    (tmp_path / "root").mkdir()
    bundle_path = tmp_path / "usr1-bundle.json"
    fired = []

    def kicker(event):
        if event["type"] == "step" and not fired:
            fired.append(event)
            os.kill(os.getpid(), signal.SIGUSR1)

    events.add_global_sink(kicker)
    try:
        code = cli.main([
            "--diag-out", str(bundle_path),
            "build", str(ctx), "-t", "usr1/app:1",
            "--storage", str(tmp_path / "storage"),
            "--root", str(tmp_path / "root"),
            "--dest", str(tmp_path / "out.tar")])
    finally:
        events.remove_global_sink(kicker)
    assert fired, "no step event — the kick never happened"
    assert code == 0
    assert (tmp_path / "out.tar").exists()  # the build FINISHED
    bundle = json.loads(bundle_path.read_text())
    assert bundle["reason"] == "SIGUSR1"
    # Captured mid-build: the build/stage spans were open.
    assert {"build"} <= {s["name"] for s in bundle["open_spans"]}
