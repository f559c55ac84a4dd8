"""The span-and-counter plane below ``commit_layer`` (PR 24): every
span at its boundary with its parent, the device-feed stages and the
bytes that cross, the two counters, the profiler's clock, and the names
on the device."""

import glob
import json
import os
import re
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from makisu_tpu import cli
from makisu_tpu.chunker import cdc
from makisu_tpu.chunker.service import HashService
from makisu_tpu.ops import gear, gear_pallas, sha256, sha256_pallas
from makisu_tpu.utils import events, metrics, traceexport

# span -> its parent, for the spans one layer commit opens once.
_PER_LAYER = {
    "memfs_sync": "commit_layer",
    "memfs_sync.os_sync": "memfs_sync",
    "memfs_sync.mtime_wait": "commit_layer",
    "layer_scan": "commit_layer",
    "tar_write": "commit_layer",
    "sink_finish": "commit_layer",
}


def _build(tmp_path, hasher, n, tag="spans/plane:1"):
    """Build ``tmp_path/ctx`` (two COPY layers) for the n-th time;
    returns (event log, report)."""
    report = tmp_path / f"report{n}.json"
    log = tmp_path / f"events{n}.jsonl"
    code = cli.main([
        "--log-level", "error", "--metrics-out", str(report),
        "--events-out", str(log),
        "build", str(tmp_path / "ctx"), "-t", tag,
        "--storage", str(tmp_path / "storage"),
        "--root", str(tmp_path / "root"), "--hasher", hasher])
    assert code == 0
    with open(report, encoding="utf-8") as f:
        return events.read_jsonl(str(log)), json.load(f)


def _context(tmp_path):
    """Two directories of one file, written ten seconds ago."""
    ctx = tmp_path / "ctx"
    for seed, name in enumerate(("a", "b")):
        (ctx / name).mkdir(parents=True)
        (ctx / name / "f.bin").write_bytes(
            np.random.default_rng(seed).bytes(40_000))
        then = time.time() - 10
        os.utime(ctx / name / "f.bin", (then, then))
    (ctx / "Dockerfile").write_text(
        "FROM scratch\nCOPY a /a/\nCOPY b /b/\n")
    (tmp_path / "root").mkdir()


def _spans(event_log):
    """[(name, parent name, attrs at start and end, duration, span_id,
    parent_id)] in opening order."""
    names = {e["span_id"]: e["name"] for e in event_log
             if e["type"] == "span_start"}
    ends = {e["span_id"]: e for e in event_log if e["type"] == "span_end"}
    return [(e["name"], names.get(e["parent_id"], ""),
             {**(e.get("attrs") or {}),
              **(ends[e["span_id"]].get("attrs") or {})},
             ends[e["span_id"]]["duration"], e["span_id"], e["parent_id"])
            for e in event_log if e["type"] == "span_start"]


def _counter(report, name, **labels):
    return sum(s["value"] for s in report["counters"].get(name, [])
               if all(s["labels"].get(k) == v for k, v in labels.items()))


@pytest.fixture
def no_sleep(monkeypatch):
    """Layer commits without the one-second mtime wait."""
    from makisu_tpu.snapshot import memfs
    original = memfs.MemFS.__init__

    def init(self, *args, **kwargs):
        original(self, *args, **kwargs)
        self.sync_wait = 0.05
    monkeypatch.setattr(memfs.MemFS, "__init__", init)


@pytest.mark.parametrize("hasher", ["cpu", "tpu"])
def test_each_boundary_span_once_per_layer_under_its_parent(
        tmp_path, hasher):
    """``--hasher cpu`` opens every span of the table but
    ``chunk_index`` (the chunk store attaches to the tpu hasher; here
    its native route). The build keeps its one second of ``sync_wait``
    and has nothing to wait for: the context is ten seconds old."""
    _context(tmp_path)
    event_log, report = _build(tmp_path, hasher, 1)
    spans = _spans(event_log)
    commits = [s for s in spans if s[0] == "commit_layer"]
    assert len(commits) == 2
    for commit in commits:
        children = [s for s in spans if s[5] == commit[4]]
        sync_id = next(s[4] for s in children if s[0] == "memfs_sync")
        family = children + [s for s in spans if s[5] == sync_id]
        assert sorted(s[0] for s in family) == sorted(_PER_LAYER)
        for name, parent, *_ in family:
            assert parent == _PER_LAYER[name]
    by_name = {}
    for s in spans:
        by_name.setdefault(s[0], []).append(s)
    assert [s[1] for s in by_name["session_begin"]] == ["build"]
    assert [s[1] for s in by_name["session_finish"]] == ["build"]
    assert [s[1] for s in by_name["copy_checksum"]] == ["context_scan"] * 2
    assert all(s[2]["files"] == "1" for s in by_name["copy_checksum"])
    assert by_name["session_begin"][0][2]["mode"] == "rescan"
    for name, attr in (("layer_scan", "entries"), ("tar_write", "bytes"),
                       ("sink_finish", "chunks")):
        assert all(int(s[2][attr]) >= 0 for s in by_name[name])
    assert all(int(s[2]["bytes"]) > 40_000 for s in by_name["tar_write"])
    assert [s[2]["wait_s"] for s in by_name["memfs_sync.mtime_wait"]] \
        == ["0.000"] * 2
    assert _counter(report, metrics.MTIME_WAIT_TOTAL, result="clear") == 2
    assert _counter(report, metrics.MTIME_WAIT_TOTAL, result="slept") == 0
    # One pair of clock reads: the stage counter is the spans' sum.
    assert _counter(report, metrics.COMMIT_STAGE_BUSY, stage="tar_write") \
        == pytest.approx(sum(s[3] for s in by_name["tar_write"]), abs=1e-5)
    if hasher == "cpu":
        assert "chunk_index" not in by_name
    else:
        assert [s[1] for s in by_name["chunk_index"]] == ["step", "step"]
        for s in by_name["chunk_index"]:
            assert int(s[2]["chunks"]) >= int(s[2]["added"]) >= 1
            assert int(s[2]["bytes_added"]) >= int(s[2]["added"])
            assert 1 <= int(s[2]["ingest_window"]) <= 8


def test_the_sync_precedes_the_scan_and_the_wait_follows_the_tar(
        tmp_path, no_sleep):
    """Context files dated ahead of the clock: each layer syncs
    before its scan and waits, for as long as ``no_sleep`` lets it,
    after its tar is written."""
    _context(tmp_path)
    ahead = time.time() + 3600
    for name in ("a", "b"):
        os.utime(tmp_path / "ctx" / name / "f.bin", (ahead, ahead))
    spans = _spans(_build(tmp_path, "cpu", 1)[0])
    commits = [s for s in spans if s[0] == "commit_layer"]
    assert len(commits) == 2
    for commit in commits:
        children = [s for s in spans if s[5] == commit[4]]
        assert [s[0] for s in children] == [
            "memfs_sync", "layer_scan", "tar_write",
            "memfs_sync.mtime_wait", "sink_finish"]
        sync, wait = children[0], children[3]
        assert [s[0] for s in spans if s[5] == sync[4]] \
            == ["memfs_sync.os_sync"]
        assert wait[2]["wait_s"] == "0.050"
        assert wait[3] >= 0.05


def test_the_phases_of_a_build_once_each_under_the_root_in_order(
        tmp_path, no_sleep):
    """What ``build`` does itself has a name: the root span's children
    are the build's phases, each once, and the root's own seconds (its
    ``self_seconds``) are what is left between them."""
    _context(tmp_path)
    event_log, report = _build(tmp_path, "cpu", 1)
    spans = _spans(event_log)
    [root] = [s for s in spans if s[1] == ""]
    assert root[0] == "build"
    assert [s[0] for s in spans if s[5] == root[4]] == [
        "build_setup", "session_begin", "context_scan", "stage",
        "wait_for_push", "save_manifest", "session_finish",
        "build_teardown"]
    [top] = report["spans"]
    covered = sum(c["duration"] for c in top["children"])
    assert top["self_seconds"] == pytest.approx(
        top["duration"] - covered, abs=1e-4)
    [ended] = [e for e in event_log
               if e["type"] == "span_end" and e["name"] == "build"]
    assert ended["self_seconds"] == pytest.approx(top["self_seconds"],
                                                  abs=1e-5)
    [manifest] = [s for s in spans if s[0] == "save_manifest"]
    assert manifest[2]["replicas"] == "0"
    # The structural spans' self time, and no other span's, is counted.
    by_span = {s["labels"]["span"]: s["value"] for s in
               report["counters"][metrics.SPAN_SELF_SECONDS]}
    assert sorted(by_span) == ["build", "stage", "step"]
    assert by_span["build"] == pytest.approx(top["self_seconds"], abs=1e-5)


def _burn(cpu_seconds):
    """Spin until this thread's own CPU clock has moved that far."""
    end = time.thread_time() + cpu_seconds
    while time.thread_time() < end:
        pass


def _self_cpu(body):
    """Run ``body`` under a registry of its own; returns ({span name:
    its ``span_end`` events}, growth of the self-CPU counter by span)."""
    seen = []
    reg = metrics.MetricsRegistry()
    reg_token = metrics.set_build_registry(reg)
    sink_token = events.add_sink(seen.append)
    try:
        body()
    finally:
        events.reset_sink(sink_token)
        metrics.reset_build_registry(reg_token)
    ends = {}
    for e in seen:
        if e["type"] == "span_end":
            ends.setdefault(e["name"], []).append(e)
    return ends, reg.counter_by_label(metrics.SPAN_SELF_CPU_SECONDS, "span")


def _a_childs_cpu_is_the_childs():
    def body():
        with metrics.span("step", structural=True):
            _burn(0.03)
            with metrics.span("commit_layer"):
                _burn(0.15)
    ends, counted = _self_cpu(body)
    [step] = ends["step"]
    assert 0.03 <= step["thread_cpu_self_seconds"] < 0.10
    assert step["self_seconds"] >= step["thread_cpu_self_seconds"] - 1e-3
    # The child read the clock for its parent's sake and says nothing.
    assert "thread_cpu_self_seconds" not in ends["commit_layer"][0]
    assert counted == {"step": pytest.approx(
        step["thread_cpu_self_seconds"], abs=1e-5)}


def _a_sleep_is_self_time_and_no_cpu():
    def body():
        with metrics.span("stage", structural=True):
            _burn(0.02)
            time.sleep(0.2)
            with metrics.span("pull_cache_layers"):
                time.sleep(0.01)
    ends, _ = _self_cpu(body)
    [stage] = ends["stage"]
    assert stage["self_seconds"] >= 0.22
    assert 0.02 <= stage["thread_cpu_self_seconds"] < 0.12


def _a_structural_child_keeps_its_own():
    def body():
        with metrics.span("build", structural=True):
            _burn(0.02)
            with metrics.span("stage", structural=True):
                _burn(0.12)
    ends, counted = _self_cpu(body)
    assert 0.02 <= ends["build"][0]["thread_cpu_self_seconds"] < 0.08
    assert ends["stage"][0]["thread_cpu_self_seconds"] >= 0.12
    assert sorted(counted) == ["build", "stage"]


def _only_a_structural_span_and_its_children_read_the_clock():
    spans = {}

    def body():
        with metrics.span("plain") as plain:
            with metrics.span("step", structural=True) as step:
                with metrics.span("commit_layer") as commit:
                    with metrics.span("tar_write") as tar:
                        spans.update(plain=plain, step=step, commit=commit,
                                     tar=tar)
    ends, counted = _self_cpu(body)
    assert {name: s._cpu0 is not None for name, s in spans.items()} \
        == {"plain": False, "step": True, "commit": True, "tar": False}
    assert sorted(counted) == ["step"]
    for name in ("plain", "commit_layer", "tar_write"):
        assert "thread_cpu_self_seconds" not in ends[name][0]


def _a_span_closed_on_another_thread_records_none():
    """Two threads' CPU clocks have nothing to do with each other: the
    difference would be a number, and wrong."""
    import contextvars
    import threading
    managers = {}
    opened, closed = threading.Event(), threading.Event()

    def open_them():
        managers["outer"] = metrics.span("build", structural=True)
        managers["outer"].__enter__()
        managers["inner"] = metrics.span("stage", structural=True)
        managers["inner"].__enter__()
        _burn(0.05)

    def opener(context):
        context.run(open_them)
        opened.set()
        # Alive until the other has closed them: a thread's ident is
        # given out again once it has ended.
        assert closed.wait(timeout=30)

    def closer(context):
        assert opened.wait(timeout=30)
        _burn(0.02)
        for name in ("inner", "outer"):
            context.run(managers[name].__exit__, None, None, None)
        closed.set()

    def body():
        # One context for both threads, copied where the registry and
        # the sink are bound: the second resets what the first set.
        context = contextvars.copy_context()
        threads = [threading.Thread(target=fn, args=(context,))
                   for fn in (opener, closer)]
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join(timeout=60)
            assert not thread.is_alive()
        assert closed.is_set()
    ends, counted = _self_cpu(body)
    for name in ("build", "stage"):
        [ended] = ends[name]
        assert ended["duration"] > 0
        assert "thread_cpu_self_seconds" not in ended
    assert counted == {}


@pytest.mark.parametrize("case", [
    _a_childs_cpu_is_the_childs, _a_sleep_is_self_time_and_no_cpu,
    _a_structural_child_keeps_its_own,
    _only_a_structural_span_and_its_children_read_the_clock,
    _a_span_closed_on_another_thread_records_none],
    ids=lambda f: f.__name__.strip("_"))
def test_a_structural_spans_self_time_on_the_threads_cpu_clock(case):
    case()


def test_a_builds_structural_spans_carry_their_cpu_self_time(
        tmp_path, no_sleep):
    _context(tmp_path)
    event_log, report = _build(tmp_path, "cpu", 1)
    ended = [e for e in event_log if e["type"] == "span_end"]
    marked = [e for e in ended if "thread_cpu_self_seconds" in e]
    assert sorted({e["name"] for e in marked}) == ["build", "stage", "step"]
    for e in marked:
        assert 0.0 <= e["thread_cpu_self_seconds"] \
            <= e.get("self_seconds", e["duration"]) + 0.005
    by_span = {s["labels"]["span"]: s["value"] for s in
               report["counters"][metrics.SPAN_SELF_CPU_SECONDS]}
    assert sorted(by_span) == ["build", "stage", "step"]
    for name, value in by_span.items():
        assert value == pytest.approx(sum(
            e["thread_cpu_self_seconds"] for e in marked
            if e["name"] == name), abs=1e-4)
    [top] = report["spans"]
    assert top["thread_cpu_self_seconds"] == pytest.approx(
        by_span["build"], abs=1e-5)


def test_the_sessions_release_covers_the_setup_spans_close(tmp_path):
    """The lease is taken inside ``build_setup``; an exit that lands in
    that span's close (a signal handler's, during the frame's write)
    still reaches the ``finally`` that releases the session."""
    from makisu_tpu.worker import session as session_mod
    _context(tmp_path)

    def dies_at_the_close(event):
        if event["type"] == "span_end" and event["name"] == "build_setup":
            raise SystemExit("terminated")

    mgr = session_mod.SessionManager()
    mgr_token = session_mod.bind_manager(mgr)
    sink_token = events.add_sink(dies_at_the_close)
    try:
        with pytest.raises(SystemExit, match="terminated"):
            cli.main([
                "--log-level", "error", "build", str(tmp_path / "ctx"),
                "-t", "spans/plane:lease",
                "--storage", str(tmp_path / "storage"),
                "--root", str(tmp_path / "root"), "--hasher", "cpu"])
    finally:
        events.reset_sink(sink_token)
        session_mod.reset_manager(mgr_token)
    [leased] = mgr._sessions.values()
    assert not leased.busy


def _replay_counts(report):
    return {result: _counter(report, metrics.LAYER_REPLAY_TOTAL,
                             result=result)
            for result in ("inflate", "memo", "unread")}


def test_layer_replay_counter_and_inflate_span(tmp_path, no_sleep):
    """Rebuilds in one process with the last layer edited before each:
    its commit reads the tree, so the cached first layer is applied
    there, under ``commit_layer``. The first rebuild inflates it
    (``apply_layer.inflate`` under ``apply_layer``), the second replays
    it from the session's memo; each edit shows in the dirty set's
    counter and span."""
    _context(tmp_path)
    _build(tmp_path, "cpu", 1)
    (tmp_path / "ctx" / "b" / "f.bin").write_bytes(b"edited")
    event_log, report = _build(tmp_path, "cpu", 2)
    spans = _spans(event_log)
    assert [s[1] for s in spans if s[0] == "apply_layer"] \
        == ["commit_layer"]
    assert [s[1] for s in spans if s[0] == "apply_layer.inflate"] \
        == ["apply_layer"]
    assert _replay_counts(report) == {"inflate": 1, "memo": 0, "unread": 0}
    assert _counter(report, metrics.CACHED_LAYERS_APPLIED_TOTAL) == 1

    (tmp_path / "ctx" / "b" / "f.bin").write_bytes(b"edited again")
    event_log, report = _build(tmp_path, "cpu", 3)
    spans = _spans(event_log)
    [apply] = [s for s in spans if s[0] == "apply_layer"]
    assert apply[1] == "commit_layer" and apply[2]["replay"] == "True"
    assert not [s for s in spans if s[0] == "apply_layer.inflate"]
    assert _replay_counts(report) == {"inflate": 0, "memo": 1, "unread": 0}
    assert _counter(report, metrics.CACHED_LAYERS_APPLIED_TOTAL) == 1
    [begin] = [s for s in spans if s[0] == "session_begin"]
    dirty = _counter(report, metrics.SESSION_DIRTY_PATHS)
    assert dirty >= 1
    assert begin[2] == {"mode": "resident", "dirty": str(int(dirty))}


def test_an_unchanged_rebuild_applies_no_layer(tmp_path, no_sleep):
    """Rebuilds of an unchanged context in one process: every step is
    a cache hit, nothing reads the tree, and both cached layers are
    dropped unread: no ``apply_layer`` span, with or without the
    session's memo."""
    _context(tmp_path)
    _build(tmp_path, "cpu", 1)
    for n in (2, 3):
        event_log, report = _build(tmp_path, "cpu", n)
        assert not [s for s in _spans(event_log)
                    if s[0].startswith("apply_layer")]
        assert _replay_counts(report) \
            == {"inflate": 0, "memo": 0, "unread": 2}
        assert _counter(report, metrics.CACHED_LAYERS_APPLIED_TOTAL) == 0
        assert _counter(report, metrics.SESSION_DIRTY_PATHS) == 0


def test_session_resync_span_when_watches_are_rebuilt(tmp_path):
    from makisu_tpu.worker import session
    watcher = session.InotifyWatcher(str(tmp_path), [])
    if not watcher.healthy:
        pytest.skip("no inotify here")
    registry = metrics.MetricsRegistry()
    token = metrics.set_build_registry(registry)
    try:
        watcher.resync()             # steady path: nothing to do
        assert not registry.root.children
        (tmp_path / "new_dir").mkdir()
        watcher.collect()
        watcher.resync()
    finally:
        metrics.reset_build_registry(token)
        watcher.close()
    [span] = registry.root.children
    assert span.name == "session_resync"
    assert span.attrs == {"watches": "2"}


def test_late_attrs_ride_span_end_into_the_trace():
    seen = []
    token = events.add_sink(seen.append)
    try:
        with metrics.span("outer", directive="COPY") as sp:
            sp.set(entries=3, bytes=1024)
        with metrics.span("plain"):
            pass
    finally:
        events.reset_sink(token)
    ends = {e["name"]: e for e in seen if e["type"] == "span_end"}
    assert ends["outer"]["attrs"] == {"entries": "3", "bytes": "1024"}
    assert "attrs" not in ends["plain"]
    tree = traceexport.assemble_fleet_trace(seen)
    [outer] = [s for s in _walk(tree) if s.get("name") == "outer"]
    assert outer["attrs"] == {"directive": "COPY", "entries": "3",
                              "bytes": "1024"}


def _walk(node):
    if isinstance(node, dict):
        yield node
        for value in node.values():
            yield from _walk(value)
    elif isinstance(node, list):
        for item in node:
            yield from _walk(item)


@pytest.mark.parametrize("name,phase", [
    ("memfs_sync.mtime_wait", "hash"), ("layer_scan", "hash"),
    ("tar_write", "hash"), ("sink_finish", "hash"),
    ("sink_finish.stream_join", "hash"), ("sink_finish.device_drain", "hash"),
    ("chunk_index", "chunk"), ("apply_layer.inflate", "other"),
    ("copy_checksum", "other"), ("session_begin", "other"),
    # PR 35: a build's set-up and tear-down are phases of their own, so
    # `/builds` says where a build is from its first span on.
    ("build_setup", "setup"), ("build_teardown", "teardown"),
    ("save_manifest", "other")])
def test_spans_under_commit_layer_keep_its_phase(name, phase):
    """`report`, `history` and the sampler split a build by phase; the
    spans that now sit inside ``commit_layer`` must not move its
    seconds out of ``hash``."""
    assert traceexport.phase_of("commit_layer") == "hash"
    assert traceexport.phase_of(name) == phase


# -- the device feed ------------------------------------------------------


def test_feed_clock_charges_a_nested_stage_to_itself(monkeypatch):
    now = [0.0]
    monkeypatch.setattr(cdc.time, "monotonic", lambda: now[0])
    clock = cdc.FeedClock()
    with clock.stage("host_cut"):
        now[0] += 1.0
        with clock.stage("sha_dispatch"):
            now[0] += 0.25
        clock.add("service_wait", 0.5)
        now[0] += 0.5 + 2.0
    with clock.stage("host_cut"):
        now[0] += 0.125
    assert dict(clock.seconds) == {
        "host_cut": 3.125, "sha_dispatch": 0.25, "service_wait": 0.5}
    clock.moved("h2d", "gear", 10)
    clock.moved("h2d", "gear", 5)
    registry = metrics.MetricsRegistry()
    token = metrics.set_build_registry(registry)
    try:
        clock.flush()
    finally:
        metrics.reset_build_registry(token)
    assert registry.counter_total(metrics.COMMIT_STAGE_BUSY,
                                  stage="host_cut") == 3.125
    assert registry.counter_total(metrics.DEVICE_TRANSFER_BYTES,
                                  direction="h2d", stage="gear") == 15
    assert not clock.seconds and not clock.bytes


@pytest.fixture
def device_route(monkeypatch):
    """The device formulation on the JAX CPU backend: the Pallas gear
    kernel in interpret mode, SHA lanes through XLA."""
    monkeypatch.setenv("MAKISU_TPU_CHUNK_NATIVE", "0")
    monkeypatch.setenv("MAKISU_TPU_PALLAS", "1")
    registry = metrics.MetricsRegistry()
    token = metrics.set_build_registry(registry)
    yield registry
    metrics.reset_build_registry(token)


_BLOCK = 128 * 1024
_STREAM = 300_000


def _expected_gear_upload():
    """Stream bytes plus each later block's halo plus the padding of
    each block's live region to the kernel's row grid."""
    total, left, first = 0, _STREAM, True
    while left:
        live = min(left, _BLOCK)
        total += (0 if first else gear_pallas.HALO) \
            + gear_pallas.padded_rows_for(live) * gear_pallas.ROW
        left -= live
        first = False
    return total


def test_chunk_session_records_the_feed_stages_and_crossings(device_route):
    payload = np.random.default_rng(24).integers(
        0, 256, size=_STREAM, dtype=np.uint8).tobytes()
    session = cdc.ChunkSession(block=_BLOCK)
    session.update(payload)
    chunks = session.finish()
    assert sum(c.length for c in chunks) == _STREAM
    for stage in ("gear_dispatch", "gear_readback", "host_cut",
                  "sha_dispatch", "sha_readback"):
        assert device_route.counter_total(
            metrics.COMMIT_STAGE_BUSY, stage=stage) > 0, stage
    assert device_route.counter_total(
        metrics.COMMIT_STAGE_BUSY, stage="service_wait") == 0

    def moved(direction, stage):
        return device_route.counter_total(
            metrics.DEVICE_TRANSFER_BYTES, direction=direction, stage=stage)
    assert _expected_gear_upload() == 327_936
    assert moved("h2d", "gear") == _expected_gear_upload()
    # One packed bit per position of every row the kernel wrote.
    rows = sum(gear_pallas.padded_rows_for(n)
               for n in (_BLOCK, _BLOCK, _STREAM - 2 * _BLOCK))
    assert moved("d2h", "gear") == rows * gear_pallas.ROW // 8
    # Each bucket flushed once: its lane buffer and its lengths up,
    # eight words a lane down. The old counter is the buffers alone.
    lanes = [(cap, n) for cap, n in cdc._BUCKETS]
    assert moved("h2d", "sha") == sum(n * cap + 4 * n for cap, n in lanes)
    assert moved("d2h", "sha") == sum(32 * n for _, n in lanes)
    assert device_route.counter_total(metrics.DEVICE_H2D_BYTES) \
        == sum(n * cap for cap, n in lanes)


def test_service_route_records_the_wait_and_the_dispatchers_side(
        device_route):
    payload = np.random.default_rng(25).integers(
        0, 256, size=_STREAM, dtype=np.uint8).tobytes()
    g = metrics.global_registry()
    before = {stage: g.counter_total(metrics.COMMIT_STAGE_BUSY, stage=stage)
              for stage in ("sha_dispatch", "sha_readback")}
    up = g.counter_total(metrics.DEVICE_TRANSFER_BYTES,
                         direction="h2d", stage="sha")
    service = HashService(linger_seconds=0.02)
    try:
        session = cdc.ChunkSession(block=_BLOCK, service=service)
        session.update(payload)
        assert sum(c.length for c in session.finish()) == _STREAM
    finally:
        service.close()
    # The build's side: it waited for the service, and fed the gear
    # scan itself.
    assert device_route.counter_total(
        metrics.COMMIT_STAGE_BUSY, stage="service_wait") > 0
    assert device_route.counter_total(
        metrics.DEVICE_TRANSFER_BYTES, direction="h2d",
        stage="gear") == _expected_gear_upload()
    assert device_route.counter_total(
        metrics.COMMIT_STAGE_BUSY, stage="sha_readback") == 0
    # The dispatcher's side runs outside any build: process totals.
    for stage, was in before.items():
        assert g.counter_total(metrics.COMMIT_STAGE_BUSY, stage=stage) > was
    assert g.counter_total(metrics.DEVICE_TRANSFER_BYTES,
                           direction="h2d", stage="sha") > up


# -- the profiler's clock -------------------------------------------------


def test_span_without_a_factory_imports_no_jax():
    code = (
        "import sys\n"
        "from makisu_tpu.utils import metrics\n"
        "with metrics.span('a', k=1) as sp:\n"
        "    with metrics.annotation('gear_dispatch'):\n"
        "        sp.set(n=2)\n"
        "assert sp.duration is not None\n"
        "assert not [m for m in sys.modules if m.split('.')[0] == 'jax']\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


@pytest.fixture
def on_profiler_clock():
    was = metrics._annotation_factory
    metrics.set_annotation_factory(jax.profiler.TraceAnnotation)
    yield
    metrics.set_annotation_factory(was)


def test_span_shows_on_the_profilers_host_plane(tmp_path, on_profiler_clock):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with metrics.span("plane_probe", directive="COPY") as sp:
            with metrics.annotation("gear_dispatch"):
                pass
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    found = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in ("plane_probe", "gear_dispatch"):
                        found[ev.name] = (ev, dict(ev.stats))
    ev, stats = found["plane_probe"]
    assert stats["span_id"] == sp.span_id
    assert stats["trace_id"] == sp.registry.trace_id
    assert ev.duration_ns / 1e9 == pytest.approx(sp.duration, abs=0.05)
    inner, inner_stats = found["gear_dispatch"]
    assert "span_id" not in inner_stats
    assert ev.start_ns <= inner.start_ns \
        and inner.start_ns + inner.duration_ns <= ev.start_ns + ev.duration_ns


def test_backend_probe_installs_the_factory(monkeypatch):
    from makisu_tpu.ops import backend
    monkeypatch.setattr(metrics, "_annotation_factory", None)
    backend._annotate_spans(jax)
    assert metrics._annotation_factory is jax.profiler.TraceAnnotation


# -- names on the device --------------------------------------------------

_U8 = np.uint8
_NAMED = [
    ("gear_scan", gear.gear_bitmap,
     (jax.ShapeDtypeStruct((2048,), _U8),), {}),
    ("gear_scan", gear_pallas.gear_bitmap_flat,
     (jax.ShapeDtypeStruct((gear_pallas.HALO + 65536,), _U8),
      gear_pallas.HALO), {"interpret": True}),
    ("chunk_sha", sha256.sha256_lanes,
     (jax.ShapeDtypeStruct((8, 128), _U8),
      jax.ShapeDtypeStruct((8,), np.int32)), {}),
    ("chunk_sha", sha256_pallas.sha256_lanes_pallas,
     (jax.ShapeDtypeStruct((8, 128), _U8),
      jax.ShapeDtypeStruct((8,), np.int32)), {"interpret": True}),
]


@pytest.mark.parametrize("scope,fn,args,static", _NAMED,
                         ids=["gear_xla", "gear_pallas", "sha_xla",
                              "sha_pallas"])
def test_every_operation_of_a_step_carries_its_scope(scope, fn, args,
                                                     static):
    text = fn.lower(*args, **static).as_text(debug_info=True)
    # The step's own operations (a jitted helper such as jnp.where is
    # lowered once, with paths relative to itself).
    paths = re.findall(rf'loc\("(jit\({fn.__name__}\)[^"]*)"', text)
    assert len(paths) > 10
    assert all(f"/{scope}" in p for p in paths), \
        [p for p in paths if f"/{scope}" not in p][:3]
