"""Worker-mode tests: build over the unix socket, end to end."""

import pytest

from makisu_tpu.worker import WorkerClient, WorkerServer


@pytest.fixture
def worker(tmp_path):
    server = WorkerServer(str(tmp_path / "worker.sock"))
    thread = server.serve_background()
    yield server
    server.shutdown()
    server.server_close()
    thread.join(timeout=5)


def test_ready(worker):
    client = WorkerClient(worker.socket_path)
    assert client.ready()


def test_not_ready_when_absent(tmp_path):
    assert not WorkerClient(str(tmp_path / "nope.sock")).ready()


def test_build_through_worker(tmp_path, worker):
    ctx = tmp_path / "ctx"
    ctx.mkdir()
    (ctx / "Dockerfile").write_text(
        "FROM scratch\nCOPY data.txt /data.txt\n")
    (ctx / "data.txt").write_text("payload")
    (tmp_path / "root").mkdir()
    client = WorkerClient(worker.socket_path)
    code = client.build([
        "build", str(ctx), "-t", "worker/test:1",
        "--storage", str(tmp_path / "storage"),
        "--root", str(tmp_path / "root"),
        "--dest", str(tmp_path / "out.tar"),
    ])
    assert code == 0
    assert (tmp_path / "out.tar").exists()


def test_build_failure_code(tmp_path, worker):
    client = WorkerClient(worker.socket_path)
    code = client.build(["build", "/nonexistent-ctx", "-t", "x:y",
                         "--storage", str(tmp_path / "s"),
                         "--root", str(tmp_path / "r")])
    assert code == 1


def test_prepare_context_copies_into_shared(tmp_path, worker):
    shared = tmp_path / "shared"
    shared.mkdir()
    ctx = tmp_path / "myctx"
    ctx.mkdir()
    (ctx / "f").write_text("x")
    client = WorkerClient(worker.socket_path,
                          local_shared_path=str(shared),
                          worker_shared_path="/mnt/shared")
    worker_path = client.prepare_context(str(ctx))
    assert worker_path == "/mnt/shared/myctx"
    assert (shared / "myctx" / "f").read_text() == "x"


def test_worker_cli_subcommand(tmp_path):
    """`makisu-tpu worker --socket ...` serves builds end to end."""
    import subprocess
    import sys
    import time

    sock = str(tmp_path / "cliworker.sock")
    proc = subprocess.Popen(
        [sys.executable, "-m", "makisu_tpu.cli", "worker",
         "--socket", sock],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    try:
        client = WorkerClient(sock)
        for _ in range(100):
            if client.ready():
                break
            time.sleep(0.1)
        assert client.ready()
        client.exit()
        proc.wait(timeout=10)
    finally:
        proc.kill()


def _file_from_save_tar(tar_path, name):
    """Read one file's bytes out of a docker-save tar's layers."""
    import io
    import json
    import tarfile
    with tarfile.open(tar_path) as tf:
        manifest = json.load(tf.extractfile("manifest.json"))
        for layer in reversed(manifest[0]["Layers"]):
            with tarfile.open(fileobj=io.BytesIO(
                    tf.extractfile(layer).read())) as lt:
                try:
                    return lt.extractfile(name).read()
                except KeyError:
                    continue
    raise KeyError(f"{name} not in any layer of {tar_path}")


def test_builds_sharing_root_serialize(tmp_path, worker):
    """Builds with the same --root must not interleave on the
    filesystem: the per-path locks serialize exactly those builds."""
    import threading

    shared_root = tmp_path / "shared-root"
    shared_root.mkdir()
    results = {}

    def one(i):
        ctx = tmp_path / f"sctx{i}"
        ctx.mkdir()
        # Each build RUNs long enough to overlap, writes a marker, and
        # then asserts no other build's marker appeared meanwhile (the
        # stage cleanup wipes the root between builds).
        (ctx / "Dockerfile").write_text(
            "FROM scratch\n"
            f"RUN echo {i} > who.txt && sleep 0.4 && "
            f"test \"$(cat who.txt)\" = \"{i}\"\n")
        client = WorkerClient(worker.socket_path)
        results[i] = client.build([
            "build", str(ctx), "-t", f"w/s{i}:1",
            "--storage", str(tmp_path / f"ss{i}"),
            "--root", str(shared_root),
            "--modifyfs"])

    threads = [threading.Thread(target=one, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # Without serialization the concurrent RUNs would clobber who.txt
    # and at least one `test` would fail.
    assert results == {0: 0, 1: 0}


def test_concurrent_build_log_streams_isolated(tmp_path, worker):
    """Each /build response streams only its own build's log lines —
    a failing build's RUN output must not leak into another client's
    stream (per-context log sinks, not a shared logging handler)."""
    import threading

    lines = {0: [], 1: []}
    results = {}

    def one(i, dockerfile):
        ctx = tmp_path / f"lctx{i}"
        ctx.mkdir()
        (ctx / "Dockerfile").write_text(dockerfile)
        (tmp_path / f"lroot{i}").mkdir()
        client = WorkerClient(worker.socket_path)
        results[i] = client.build([
            "build", str(ctx), "-t", f"w/log{i}:1",
            "--storage", str(tmp_path / f"ls{i}"),
            "--root", str(tmp_path / f"lroot{i}"),
            "--modifyfs"],
            on_line=lambda p, i=i: lines[i].append(p.get("msg", "")))

    threads = [
        threading.Thread(target=one, args=(
            0, "FROM scratch\nRUN echo MARKER-GOOD-BUILD\n"
               "RUN sleep 0.5\nRUN echo DONE-GOOD\n")),
        threading.Thread(target=one, args=(
            1, "FROM scratch\nRUN echo MARKER-BAD-BUILD\n"
               "RUN sleep 0.2 && echo FAILING-NOW && false\n")),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert results[0] == 0
    assert results[1] == 1
    good = "\n".join(lines[0])
    bad = "\n".join(lines[1])
    assert "MARKER-GOOD-BUILD" in good
    assert "MARKER-BAD-BUILD" in bad and "FAILING-NOW" in bad
    # No cross-talk in either direction.
    assert "MARKER-BAD-BUILD" not in good and "FAILING-NOW" not in good
    assert "MARKER-GOOD-BUILD" not in bad


def test_concurrent_builds_run_in_parallel(tmp_path, worker):
    """Simultaneous /build requests run concurrently with isolated
    ARG/ENV: each build's RUN step must see its own values (step env
    lives in the BuildContext, never os.environ)."""
    import threading

    results = {}

    def one(i):
        ctx = tmp_path / f"ctx{i}"
        ctx.mkdir()
        (ctx / "Dockerfile").write_text(
            f"FROM scratch\n"
            f"COPY f.txt /f{i}.txt\n"
            f"ENV BUILD_VAL=value-{i}\n"
            "RUN echo -n \"$BUILD_VAL\" > val.txt\n")
        (ctx / "f.txt").write_text(str(i))
        (tmp_path / f"root{i}").mkdir()
        client = WorkerClient(worker.socket_path)
        results[i] = client.build([
            "build", str(ctx), "-t", f"w/c{i}:1",
            "--storage", str(tmp_path / f"s{i}"),
            "--root", str(tmp_path / f"root{i}"),
            "--modifyfs",
            "--dest", str(tmp_path / f"out{i}.tar")])

    threads = [threading.Thread(target=one, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert results == {0: 0, 1: 0, 2: 0}
    for i in range(3):
        out = tmp_path / f"out{i}.tar"
        assert out.exists()
        # Env isolation: build i's RUN saw its own BUILD_VAL even while
        # the other builds exported theirs concurrently.
        assert _file_from_save_tar(
            str(out), "val.txt") == f"value-{i}".encode()


def test_pull_through_worker_with_per_request_config(tmp_path, worker):
    """The worker serves pull/push/diff too (any CLI argv): a pull with
    its own --registry-config must succeed without mutating the
    process-global config map (which concurrent builds read)."""
    import json

    from makisu_tpu.registry import make_test_image
    from makisu_tpu.registry.client import set_transport_factory
    from makisu_tpu.registry.config import _global_config
    from makisu_tpu.registry.fixtures import RegistryFixture

    fixture = RegistryFixture()
    manifest, _config_blob, blobs = make_test_image()
    fixture.serve_image("team/app", "v1", manifest, blobs)
    set_transport_factory(lambda name: fixture)
    try:
        before = json.dumps(_global_config, default=str, sort_keys=True)
        cfg = tmp_path / "registry.yaml"
        cfg.write_text(json.dumps(
            {"registry.test": {"team/*": {"security": {
                "tls": {"client": {"disabled": True}}}}}}))
        client = WorkerClient(worker.socket_path)
        code = client.build([
            "--log-level", "error", "pull", "registry.test/team/app:v1",
            "--storage", str(tmp_path / "storage"),
            "--registry-config", str(cfg),
        ])
        assert code == 0
        # The layer actually landed.
        import os
        layers_dir = tmp_path / "storage" / "layers"
        assert any(files for _, _, files in os.walk(layers_dir))
        # And the process-global map is untouched (no cross-request
        # contamination inside the long-lived worker).
        after = json.dumps(_global_config, default=str, sort_keys=True)
        assert after == before
    finally:
        set_transport_factory(None)


def test_build_streams_event_frames(tmp_path, worker):
    """NDJSON event framing round-trip: events emitted inside the
    worker's build ride the /build response stream as their own frame
    type and arrive as dicts — collected into ``last_events`` and
    forwarded to ``on_event`` in order."""
    ctx = tmp_path / "ectx"
    ctx.mkdir()
    (ctx / "Dockerfile").write_text(
        "FROM scratch\nCOPY data.txt /data.txt\n")
    (ctx / "data.txt").write_text("event frame payload")
    (tmp_path / "eroot").mkdir()
    client = WorkerClient(worker.socket_path)
    streamed = []
    code = client.build([
        "--metrics-out", str(tmp_path / "ereport.json"),
        "build", str(ctx), "-t", "worker/events:1",
        "--storage", str(tmp_path / "estorage"),
        "--root", str(tmp_path / "eroot"),
        "--dest", str(tmp_path / "eout.tar"),
    ], on_event=streamed.append)
    assert code == 0
    # In-worker builds label their build_info gauge mode="worker"
    # (context-scoped — no process-env mutation).
    import json as json_mod
    report = json_mod.loads((tmp_path / "ereport.json").read_text())
    [info] = report["gauges"]["makisu_build_info"]
    assert info["labels"]["mode"] == "worker"
    assert client.last_events == streamed
    types = [e["type"] for e in streamed]
    # The admission wait rides the stream as its own event, BEFORE the
    # build proper (it happened before the build's registry existed).
    assert types[0] == "queue_wait"
    assert types[1] == "build_start"
    assert types[-1] == "build_end"
    assert "span_start" in types and "span_end" in types
    assert "step" in types
    # Every frame survived JSON round-trip as a timestamped dict.
    assert all(isinstance(e["ts"], float) for e in streamed)
    # span_start/span_end pair up by span id.
    opened = [e["span_id"] for e in streamed if e["type"] == "span_start"]
    closed = [e["span_id"] for e in streamed if e["type"] == "span_end"]
    assert sorted(opened) == sorted(closed)


def test_concurrent_builds_do_not_mix_event_streams(tmp_path, worker):
    """Client A's event frames must never surface in client B's stream
    (the same isolation guarantee the log sinks give)."""
    import threading

    streams = {}

    def one(i):
        ctx = tmp_path / f"evctx{i}"
        ctx.mkdir()
        (ctx / "Dockerfile").write_text(
            "FROM scratch\nCOPY d.txt /d.txt\n")
        (ctx / "d.txt").write_text(f"payload-{i}" * 8)
        (tmp_path / f"evroot{i}").mkdir()
        client = WorkerClient(worker.socket_path)
        events = []
        code = client.build([
            "build", str(ctx), "-t", f"worker/ev{i}:1",
            "--storage", str(tmp_path / f"evstorage{i}"),
            "--root", str(tmp_path / f"evroot{i}"),
            "--dest", str(tmp_path / f"evout{i}.tar"),
        ], on_event=events.append)
        streams[i] = (code, events)

    threads = [threading.Thread(target=one, args=(i,)) for i in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    trace_ids = {}
    for i in (0, 1):
        code, events = streams[i]
        assert code == 0
        assert events, f"build {i} streamed no events"
        starts = [e for e in events if e["type"] == "build_start"]
        assert len(starts) == 1, "exactly one build_start per stream"
        trace_ids[i] = starts[0]["trace_id"]
    assert trace_ids[0] != trace_ids[1]


def test_healthz(tmp_path, worker):
    client = WorkerClient(worker.socket_path)
    before = client.healthz()
    assert before["status"] == "ok"
    assert before["uptime_seconds"] >= 0
    assert before["active_builds"] == 0
    # Failure-forensics vitals: the progress clock and the transfer
    # engine's gauges ride /healthz so a wedged worker is diagnosable
    # without scraping /metrics.
    assert before["last_progress_seconds"] >= 0
    assert before["transfer_inflight_bytes"] >= 0
    assert before["transfer_queue_depth"] >= 0
    # Device-route vitals: probe state + execution-plane aggregates
    # ride /healthz so a wedged backend init is visible to a poller
    # before any build pays the bounded wait.
    device = before["device"]
    assert device["probe"]["state"] in (
        "ok", "pending", "wedged", "failed", "absent", "disabled")
    assert "dispatch_seconds" in device
    assert device["h2d_bytes"] >= 0
    assert device["padding_waste_bytes"] >= 0
    assert before.device_probe_state == device["probe"]["state"]

    ctx = tmp_path / "hctx"
    ctx.mkdir()
    (ctx / "Dockerfile").write_text("FROM scratch\nCOPY h /h\n")
    (ctx / "h").write_text("x")
    (tmp_path / "hroot").mkdir()
    ok = client.build(["build", str(ctx), "-t", "worker/h:1",
                       "--storage", str(tmp_path / "hstorage"),
                       "--root", str(tmp_path / "hroot"),
                       "--dest", str(tmp_path / "hout.tar")])
    assert ok == 0
    bad = client.build(["build", "/nonexistent", "-t", "x:y",
                        "--storage", str(tmp_path / "hs2"),
                        "--root", str(tmp_path / "hr2")])
    assert bad == 1

    after = client.healthz()
    assert after["builds_started"] == before["builds_started"] + 2
    assert after["builds_succeeded"] == before["builds_succeeded"] + 1
    assert after["builds_failed"] == before["builds_failed"] + 1
    assert after["active_builds"] == 0
    assert after["uptime_seconds"] >= before["uptime_seconds"]
    # The builds just emitted events/logs: the progress clock is fresh.
    assert after["last_progress_seconds"] < 30
    # Transfers all settled: nothing reserved or queued.
    assert after["transfer_inflight_bytes"] == 0
    assert after["transfer_queue_depth"] == 0


def test_worker_process_recorder_captures_builds(tmp_path, worker):
    """The worker's process-level flight recorder (a global event
    sink) sees every build's events, so a SIGTERM'd worker can dump a
    bundle covering all in-flight work."""
    ctx = tmp_path / "frctx"
    ctx.mkdir()
    (ctx / "Dockerfile").write_text("FROM scratch\nCOPY f /f\n")
    (ctx / "f").write_text("x")
    (tmp_path / "frroot").mkdir()
    client = WorkerClient(worker.socket_path)
    assert client.build(["build", str(ctx), "-t", "worker/fr:1",
                         "--storage", str(tmp_path / "frstorage"),
                         "--root", str(tmp_path / "frroot")]) == 0
    bundle = worker.recorder.bundle("inspect")
    types = [e["type"] for e in bundle["events"]]
    assert "build_start" in types and "build_end" in types
    assert bundle["schema"] == "makisu-tpu.flightrecorder.v1"
    # Process bundle resolves the GLOBAL registry's trace id.
    from makisu_tpu.utils import metrics
    assert bundle["build"]["trace_id"] == \
        metrics.global_registry().trace_id


def test_worker_survives_systemexit_with_message(tmp_path, worker):
    """cmd_report raises SystemExit with a STRING (schema mismatch);
    the worker must map it to exit code 1 and keep serving — not die
    mid-stream on int(<message>)."""
    bogus = tmp_path / "bogus.json"
    bogus.write_text('{"hello": "world"}')
    client = WorkerClient(worker.socket_path)
    lines = []
    code = client.build(["report", str(bogus)], on_line=lines.append)
    assert code == 1
    assert any("not a makisu-tpu metrics report" in p.get("msg", "")
               for p in lines)
    assert client.ready()  # handler thread survived


# -- the request's own seconds (PR 35) ---------------------------------------


def _small_build(tmp_path, client, n, **kwargs):
    ctx = tmp_path / "sctx"
    if not ctx.exists():
        ctx.mkdir()
        (ctx / "Dockerfile").write_text(
            "FROM scratch\nCOPY data.txt /data.txt\n")
        (ctx / "data.txt").write_text("service payload")
    (tmp_path / f"sroot{n}").mkdir()
    code = client.build([
        "--log-level", "error", "build", str(ctx),
        "-t", f"worker/service:{n}",
        "--storage", str(tmp_path / "sstorage"),
        "--root", str(tmp_path / f"sroot{n}")], **kwargs)
    assert code == 0
    return dict(client.last_build)


def test_terminal_record_accounts_the_service_whole(tmp_path, worker):
    """``service_seconds`` is admission to the end of ``run_build``:
    set-up (before the root span opened), the root span, tear-down
    (after it closed). Nothing of a request's service is outside the
    three, and the queue wait is outside all of them."""
    client = WorkerClient(worker.socket_path)
    _small_build(tmp_path, client, 0)       # the process's first build
    terminal = _small_build(tmp_path, client, 1)
    [root] = [e for e in client.last_events
              if e["type"] == "span_end" and e["name"] == "build"]
    for field in ("setup_seconds", "teardown_seconds", "service_seconds"):
        assert terminal[field] >= 0.0
    assert terminal["service_seconds"] == pytest.approx(
        terminal["setup_seconds"] + root["duration"]
        + terminal["teardown_seconds"], abs=0.010)
    assert terminal["service_seconds"] == pytest.approx(
        terminal["elapsed_seconds"] - terminal["queue_wait_seconds"],
        abs=0.010)
    # The building thread's own CPU over the same interval: some, and
    # no more than the interval (the clocks differ in resolution).
    assert 0.0 < terminal["thread_cpu_seconds"] \
        <= terminal["service_seconds"] + 0.005
    # /builds carries the same four once the request is done.
    row = next(r for r in client.builds()["recent"]
               if r["tag"] == "worker/service:1")
    fields = ("setup_seconds", "teardown_seconds", "service_seconds",
              "thread_cpu_seconds")
    assert {k: row[k] for k in fields} == {k: terminal[k] for k in fields}
    from makisu_tpu.utils import metrics
    assert f"{metrics.WORKER_BUILD_THREAD_CPU_SECONDS} " in client.metrics()


def test_a_request_that_opens_no_root_span_is_set_up_all_through(
        tmp_path, worker):
    client = WorkerClient(worker.socket_path)
    assert client.build(["build", "--no-such-flag"]) != 0
    terminal = client.last_build
    assert terminal["teardown_seconds"] == 0.0
    assert terminal["setup_seconds"] == terminal["service_seconds"] > 0.0


def test_worker_builds_hang_nothing_on_the_process_registrys_root(
        tmp_path, worker):
    """The request's set-up and tear-down are clock reads, not spans:
    no build registry is bound in ``run_build``, and what hangs on the
    process registry's root is never pruned."""
    from makisu_tpu.utils import metrics
    client = WorkerClient(worker.socket_path)
    _small_build(tmp_path, client, 0)
    root = metrics.global_registry().root
    before = len(root.children)
    for n in range(1, 4):
        _small_build(tmp_path, client, n)
    assert len(root.children) == before


def test_builds_phase_is_named_from_the_first_span_on():
    from makisu_tpu.worker.server import _BuildRecord
    record = _BuildRecord(1, "", ["build"])
    seen = []
    for name in ("build", "build_setup", "session_begin", "context_scan",
                 "commit_layer", "wait_for_push", "save_manifest",
                 "build_teardown"):
        record.note_event({"type": "span_start", "name": name})
        seen.append(record.to_dict()["phase"])
    assert seen == ["", "setup", "setup", "setup", "hash", "push", "push",
                    "teardown"]


# -- a request is resolved once (PR 45) --------------------------------------


@pytest.fixture
def server(tmp_path):
    """A worker nobody connects to: ``run_build`` is called directly,
    on the test's thread."""
    srv = WorkerServer(str(tmp_path / "direct.sock"))
    yield srv
    srv.server_close()


def _direct_argv(tmp_path, storage="dstorage", root="droot"):
    ctx = tmp_path / "dctx"
    if not ctx.exists():
        ctx.mkdir()
        (ctx / "Dockerfile").write_text(
            "FROM scratch\nCOPY data.txt /data.txt\n")
        (ctx / "data.txt").write_text("resolved once")
    (tmp_path / root).mkdir(exist_ok=True)
    return ["--log-level", "error", "build", str(ctx), "-t", "w/direct:1",
            "--storage", str(tmp_path / storage),
            "--root", str(tmp_path / root)]


def _resolve_total(kind, result):
    from makisu_tpu.utils import metrics
    return metrics.global_registry().counter_total(
        metrics.REQUEST_RESOLVE_TOTAL, kind=kind, result=result)


def test_a_request_builds_no_parser_and_parses_once(
        tmp_path, server, monkeypatch):
    """Once the process has its parser, a request through ``run_build``
    builds no tree (neither ``make_parser`` nor what it is made of) and
    ``argv`` is parsed once: ``cli.main`` takes the namespace."""
    import argparse

    from makisu_tpu import cli
    argv = _direct_argv(tmp_path)
    assert server.run_build(argv, lambda line: None) == 0
    calls = {"make_parser": 0, "_parser_tree": 0, "parse": 0}

    def counted(name, fn):
        def wrapper(*args, **kwargs):
            calls[name] += 1
            return fn(*args, **kwargs)
        return wrapper

    monkeypatch.setattr(cli, "make_parser",
                        counted("make_parser", cli.make_parser))
    monkeypatch.setattr(cli, "_parser_tree",
                        counted("_parser_tree", cli._parser_tree))
    # A sub-parser is entered through parse_known_args, so this counts
    # whole parses of a command line.
    monkeypatch.setattr(argparse.ArgumentParser, "parse_args", counted(
        "parse", argparse.ArgumentParser.parse_args))
    before = {(k, r): _resolve_total(k, r) for k in ("parse", "realpath")
              for r in ("done", "reused")}
    assert server.run_build(argv, lambda line: None) == 0
    assert calls == {"make_parser": 0, "_parser_tree": 0, "parse": 1}
    grown = {key: _resolve_total(*key) - was
             for key, was in before.items()}
    # One parse; the locks and cli.main were answered by the record.
    assert grown["parse", "done"] == 1
    assert grown["parse", "reused"] == 2
    # --root, --storage and the context walked once each, and one
    # lstat for <storage>/chunks; every other question was answered.
    assert grown["realpath", "done"] == 4
    assert grown["realpath", "reused"] >= 10


@pytest.mark.parametrize("spelling", [
    "separate", "equals", "abbreviated", "ambiguous", "default",
    "symlinked_root", "relative", "no_paths"])
def test_lock_keys_are_the_parents(tmp_path, server, monkeypatch,
                                   spelling):
    """``_shared_path_locks`` keys, as at 4589821: the real parser's
    reading of --root and --storage (equals form, abbreviations, the
    computed default), canonicalised through symlinks."""
    import os

    from makisu_tpu import cli
    from makisu_tpu.worker import server as server_mod
    storage = tmp_path / "lock storage"
    storage.mkdir()
    root = tmp_path / "lockroot"
    root.mkdir()
    real_storage = os.path.realpath(storage)
    real_root = os.path.realpath(root)
    head = ["build", str(tmp_path), "-t", "w/l:1"]
    if spelling == "separate":
        argv = head + ["--storage", str(storage), "--root", str(root)]
    elif spelling == "equals":
        argv = head + [f"--storage={storage}", f"--root={root}"]
    elif spelling == "abbreviated":
        # (--stor is ambiguous since --storage-budget: "ambiguous".)
        argv = head + ["--storage", str(storage), "--roo", str(root)]
    elif spelling == "ambiguous":
        # Malformed for argparse: cli.main reports it, nothing is
        # touched, and the keys are those of a request with no paths.
        argv = head + ["--stor", str(storage), "--root", str(root)]
        real_root = real_storage = None
    elif spelling == "default":
        argv = head + ["--root", str(root)]
        real_storage = os.path.realpath(cli._storage_dir(""))
    elif spelling == "symlinked_root":
        link = tmp_path / "rootlink"
        link.symlink_to(root)
        argv = head + ["--storage", str(storage), "--root", str(link)]
    elif spelling == "relative":
        monkeypatch.chdir(tmp_path)
        argv = head + ["--storage", "lock storage", "--root", "lockroot"]
    else:
        argv = ["version"]
        real_root = real_storage = None
    flags = server_mod._effective_flags(argv)
    locks = server._shared_path_locks(flags)
    # The table is the process's: read the keys these locks stand under.
    keys = {key for key, lock in server._path_locks.items()
            if any(lock is mine for mine in locks)}
    assert keys == {"--root=" + (real_root or "<none>"),
                    "--storage=" + (real_storage or "<none>")}
    assert len(locks) == 2
    # The same locks for the same request, whoever asks again.
    assert server._shared_path_locks(
        server_mod._effective_flags(argv)) == locks
    if spelling == "default":
        # The computed default reaches the command in the namespace.
        assert flags.args.storage == cli._storage_dir("")


def test_malformed_argv_ends_with_argparses_message(tmp_path, worker,
                                                    capfd):
    """A malformed ``argv`` reaches ``cli.main``'s own parse: argparse's
    message on the worker's standard error, its exit code (2) in the
    client's terminal record, and the worker keeps serving."""
    client = WorkerClient(worker.socket_path)
    code = client.build(["build", str(tmp_path), "-t", "w/m:1",
                         "--commit", "sometimes"])
    assert code == 2
    assert client.last_build["exit_code"] == 2
    err = capfd.readouterr().err
    assert "invalid choice: 'sometimes'" in err
    assert "usage: makisu-tpu build" in err
    assert client.ready()


def test_what_a_request_resolved_dies_with_it(tmp_path, server):
    """Build into storage ``S``, remove ``S``, make ``S`` a symlink to
    another directory, build again through the same worker: the second
    build's outputs land in the new target and its lock key is the new
    real path. Nothing resolved for one request is seen by the next."""
    import os
    import shutil

    from makisu_tpu.utils import pathutils
    from makisu_tpu.worker import server as server_mod
    argv = _direct_argv(tmp_path, storage="S")
    storage = tmp_path / "S"
    assert server.run_build(argv, lambda line: None) == 0
    assert pathutils._request_dirs.get() is None
    assert os.listdir(storage / "layers")
    assert f"--storage={storage}" in server._path_locks
    shutil.rmtree(storage)
    target = tmp_path / "elsewhere"
    target.mkdir()
    storage.symlink_to(target)
    assert server.run_build(argv, lambda line: None) == 0
    assert os.listdir(target / "layers")
    assert f"--storage={target}" in server._path_locks
    flags = server_mod._effective_flags(argv)
    assert flags.dirs[flags.storage] == str(target)
    assert str(target) in server.storage_dirs()


def test_a_warm_requests_setup_stats_a_third_of_the_parents(
        tmp_path, server, monkeypatch):
    """``os.stat`` + ``os.lstat`` on the building thread from admission
    to the root span's open, over a worker's third request for one
    context under pytest's ``tmp_path``. At 4589821 this read 211:
    180 from three parser trees (``gettext.find``'s ``exists``, 60 a
    tree where ``LANG`` names a language, as here and on the chip's
    machine: ``C.UTF-8``; none where it is unset) and 31 from seven
    ``realpath`` walks of --root, --storage and ``<storage>/chunks``. Now it reads 16: the three directories are
    walked once (five components each here) and ``<storage>/chunks``
    costs one ``lstat``."""
    import os
    import threading

    argv = _direct_argv(tmp_path)
    for _ in range(2):
        assert server.run_build(argv, lambda line: None) == 0
    counting = {"on": False, "n": 0}
    me = threading.get_ident()

    def counted(fn):
        def wrapper(*args, **kwargs):
            if counting["on"] and threading.get_ident() == me:
                counting["n"] += 1
            return fn(*args, **kwargs)
        return wrapper

    monkeypatch.setattr(os, "stat", counted(os.stat))
    monkeypatch.setattr(os, "lstat", counted(os.lstat))
    acquire = server._admission.acquire

    def admitted():
        wait = acquire()
        counting["on"] = True
        return wait

    monkeypatch.setattr(server._admission, "acquire", admitted)

    def emit(line):
        if '"build_start"' in line:     # the root span opens
            counting["on"] = False

    assert server.run_build(argv, emit) == 0
    assert not counting["on"]
    assert 0 < counting["n"] <= 211 // 3
