"""Resident build-session tests: manager lifecycle (identity/TTL/LRU/
busy), the walk-based dirty-set primitives, the inotify watcher, the
statcache atomic save, and the worker's session endpoints."""

import importlib
import json
import os
import time

import pytest

from makisu_tpu import cli
from makisu_tpu.docker.image import ImageName
from makisu_tpu.storage import ImageStore
from makisu_tpu.worker import WorkerClient, WorkerServer
from makisu_tpu.worker import session as session_mod

walk_mod = importlib.import_module("makisu_tpu.snapshot.walk")


@pytest.fixture(autouse=True)
def _fresh_sessions(monkeypatch):
    """Each test starts with an empty process-global session registry
    and an exact (window-0) racy discipline so snapshots certify
    immediately."""
    monkeypatch.setenv("MAKISU_TPU_STAT_CACHE_WINDOW_NS", "0")
    session_mod.manager().reset()
    yield
    session_mod.manager().reset()


# -- walk delta primitives --------------------------------------------------


def test_snapshot_delta_detects_change_add_remove(tmp_path):
    root = tmp_path / "tree"
    (root / "a").mkdir(parents=True)
    (root / "a" / "f1").write_text("one")
    (root / "f2").write_text("two")
    snap = walk_mod.snapshot_tree(str(root))
    assert str(root / "a" / "f1") in snap.sigs
    (root / "a" / "f1").write_text("one'")
    (root / "f3").write_text("three")
    (root / "f2").unlink()
    snap2, delta = walk_mod.snapshot_delta(snap)
    assert str(root / "a" / "f1") in delta.changed
    assert str(root / "f3") in delta.added
    assert str(root / "f2") in delta.removed
    # A quiet path is not dirty.
    assert str(root / "a") not in delta.added
    # A second delta against the fresh snapshot is clean.
    _, delta2 = walk_mod.snapshot_delta(snap2)
    assert not delta2.dirty


def test_snapshot_racy_window_marks_fresh_dirty_once(tmp_path,
                                                     monkeypatch):
    """Files whose timestamps sit inside the racy window of the
    capture can't be certified — they count dirty on the next delta
    (bounded re-hash), but never trigger a watch rebuild
    (real_dirty)."""
    monkeypatch.setenv("MAKISU_TPU_STAT_CACHE_WINDOW_NS",
                       str(10**12))  # everything is "fresh"
    root = tmp_path / "tree"
    root.mkdir()
    (root / "f").write_text("x")
    snap = walk_mod.snapshot_tree(str(root))
    assert str(root / "f") in snap.fresh
    _, delta = walk_mod.snapshot_delta(snap)
    assert str(root / "f") in delta.dirty
    assert str(root / "f") not in delta.real_dirty


# -- manager lifecycle ------------------------------------------------------


def test_acquire_reuse_and_flag_identity_invalidation(tmp_path):
    ctx = tmp_path / "ctx"
    ctx.mkdir()
    mgr = session_mod.manager()
    s1, verdict = mgr.acquire(str(ctx), "identity-a")
    assert verdict == "miss" and s1 is not None
    mgr.release(s1)
    s2, verdict = mgr.acquire(str(ctx), "identity-a")
    assert verdict == "hit" and s2 is s1
    mgr.release(s2)
    s3, verdict = mgr.acquire(str(ctx), "identity-B")
    assert verdict == "miss" and s3 is not s1
    mgr.release(s3)
    assert mgr.invalidations.get("flag_identity") == 1


def test_acquire_busy_bypass(tmp_path):
    ctx = tmp_path / "ctx"
    ctx.mkdir()
    mgr = session_mod.manager()
    s1, _ = mgr.acquire(str(ctx), "id")
    s2, verdict = mgr.acquire(str(ctx), "id")
    assert s2 is None and verdict == "busy"
    mgr.release(s1)
    s3, verdict = mgr.acquire(str(ctx), "id")
    assert s3 is s1 and verdict == "hit"
    mgr.release(s3)


def test_ttl_invalidation(tmp_path, monkeypatch):
    monkeypatch.setenv("MAKISU_TPU_SESSION_TTL", "0")
    ctx = tmp_path / "ctx"
    ctx.mkdir()
    mgr = session_mod.manager()
    s1, _ = mgr.acquire(str(ctx), "id")
    mgr.release(s1)
    time.sleep(0.01)
    s2, verdict = mgr.acquire(str(ctx), "id")
    assert verdict == "miss" and s2 is not s1
    mgr.release(s2)
    assert mgr.invalidations.get("ttl") == 1


def test_lru_cap_evicts_stalest(tmp_path, monkeypatch):
    monkeypatch.setenv("MAKISU_TPU_SESSION_MAX", "1")
    a, b = tmp_path / "a", tmp_path / "b"
    a.mkdir()
    b.mkdir()
    mgr = session_mod.manager()
    s1, _ = mgr.acquire(str(a), "id")
    mgr.release(s1)
    s2, _ = mgr.acquire(str(b), "id")
    mgr.release(s2)
    assert mgr.invalidations.get("lru") == 1
    assert mgr.peek(str(a)) is None
    assert mgr.peek(str(b)) is s2


def test_explicit_invalidate(tmp_path):
    ctx = tmp_path / "ctx"
    ctx.mkdir()
    mgr = session_mod.manager()
    s1, _ = mgr.acquire(str(ctx), "id")
    mgr.release(s1)
    assert mgr.invalidate(str(ctx)) == 1
    assert mgr.peek(str(ctx)) is None
    assert mgr.invalidations.get("explicit") == 1


def test_stats_shape(tmp_path):
    ctx = tmp_path / "ctx"
    ctx.mkdir()
    mgr = session_mod.manager()
    s1, _ = mgr.acquire(str(ctx), "id")
    mgr.release(s1)
    stats = mgr.stats()
    assert stats["count"] == 1
    assert stats["max_sessions"] >= 1
    row = stats["sessions"][0]
    assert row["context"] == str(ctx)
    assert row["watcher"] in ("inotify", "mtime-walk")
    assert isinstance(row["resident_bytes"], int)


class _MiniCtx:
    """Just enough BuildContext surface for direct session driving."""

    def __init__(self, context_dir: str, store_root: str) -> None:
        import types
        self.context_dir = context_dir
        self.base_blacklist: list = []
        self.image_store = types.SimpleNamespace(root=store_root)
        self.content_ids = None
        self.session = None
        self.dirty_paths: frozenset = frozenset()
        self.dirty_exact = False
        self.listing = walk_mod.TreeListing(context_dir)


@pytest.mark.parametrize("watcher_mode", ["inotify", "mtime-walk"])
def test_mid_build_edit_lands_in_next_dirty_set(tmp_path, monkeypatch,
                                                watcher_mode):
    """An edit racing the build (after its scan passed the file) must
    surface in the NEXT build's dirty set — the tracker baseline is
    established BEFORE the scan, in both tracker modes."""
    if watcher_mode == "mtime-walk":
        monkeypatch.setenv("MAKISU_TPU_SESSION_MAX_WATCHES", "0")
    ctx_dir = tmp_path / "ctx"
    ctx_dir.mkdir()
    victim = ctx_dir / "f.txt"
    victim.write_text("v1")
    mgr = session_mod.manager()
    s, _ = mgr.acquire(str(ctx_dir), "id")
    ctx = _MiniCtx(str(ctx_dir), str(tmp_path / "store"))
    s.begin_build(ctx)
    if watcher_mode == "inotify" and (
            s.watcher is None or not s.watcher.healthy):
        mgr.release(s)
        pytest.skip("inotify unavailable on this host")
    # The "build" runs here; the edit lands mid-build.
    victim.write_text("v2-mid-build")
    s.finish_build(ctx, ok=True)
    mgr.release(s)
    s2, verdict = mgr.acquire(str(ctx_dir), "id")
    assert s2 is s and verdict == "hit"
    s2.begin_build(ctx)
    try:
        assert not ctx.dirty_exact or str(victim) in ctx.dirty_paths \
            or str(ctx_dir) in ctx.dirty_paths, (
            "mid-build edit was silently lost: exact dirty set "
            f"{set(ctx.dirty_paths)!r} misses {victim}")
    finally:
        s2.finish_build(ctx, ok=True)
        mgr.release(s2)


def test_watch_knowledge_loss_flags_context_dirty(tmp_path,
                                                  monkeypatch):
    """A dead tracker (here: no watcher, no baseline) must flag the
    whole context dirty once and re-seed — never silently report
    'no changes' forever."""
    monkeypatch.setenv("MAKISU_TPU_SESSION_MAX_WATCHES", "0")
    ctx_dir = tmp_path / "ctx"
    ctx_dir.mkdir()
    (ctx_dir / "f").write_text("x")
    mgr = session_mod.manager()
    s, _ = mgr.acquire(str(ctx_dir), "id")
    s._walk_blacklist = []
    s._resident_hint = True  # models a watch loop / worker session
    dirt = s.poll_changes()
    assert str(ctx_dir) in dirt  # knowledge loss → context flagged
    assert s.snapshot is not None  # ...and tracking resumed
    (ctx_dir / "f").write_text("y")
    dirt = s.poll_changes()
    assert str(ctx_dir / "f") in dirt
    mgr.release(s)


# -- inotify watcher --------------------------------------------------------


def _watcher_or_skip(root: str) -> session_mod.InotifyWatcher:
    watcher = session_mod.InotifyWatcher(root, [])
    if not watcher.healthy:
        pytest.skip("inotify unavailable on this host")
    return watcher


def test_inotify_collects_file_edits(tmp_path):
    root = tmp_path / "tree"
    (root / "sub").mkdir(parents=True)
    (root / "sub" / "f").write_text("x")
    watcher = _watcher_or_skip(str(root))
    try:
        (root / "sub" / "f").write_text("y")
        deadline = time.time() + 2.0
        dirty = set()
        while time.time() < deadline and not dirty:
            dirty |= watcher.collect() or set()
            time.sleep(0.01)
        assert str(root / "sub" / "f") in dirty
    finally:
        watcher.close()


def test_inotify_new_dir_marks_dirty_and_resyncs(tmp_path):
    root = tmp_path / "tree"
    root.mkdir()
    watcher = _watcher_or_skip(str(root))
    try:
        (root / "newdir").mkdir()
        time.sleep(0.05)
        dirty = watcher.collect()
        assert dirty is not None and str(root / "newdir") in dirty
        watcher.resync()
        assert watcher.healthy
        # Post-resync, events inside the new dir are observed.
        (root / "newdir" / "f").write_text("x")
        time.sleep(0.05)
        dirty = watcher.collect()
        assert dirty is not None
        assert str(root / "newdir" / "f") in dirty
    finally:
        watcher.close()


# -- statcache atomic save satellite ---------------------------------------


def test_statcache_save_atomic_and_begin_build(tmp_path):
    from makisu_tpu.utils.statcache import ContentIDCache
    path = tmp_path / "cache.json"
    cache = ContentIDCache(str(path), namespace="ns")
    (tmp_path / "f").write_text("data")
    st = os.lstat(tmp_path / "f")
    cache.put("f", st, 123)
    cache.save()
    rec = json.loads(path.read_text())
    assert rec["version"] >= 2 and "ns\x00f" in rec["entries"]
    # No stray temp files survive a successful save.
    assert [p for p in os.listdir(tmp_path)
            if p.endswith(".tmp")] == []
    assert cache._touched
    cache.begin_build()
    assert not cache._touched


def test_write_json_atomic_cleans_tmp_on_failure(tmp_path):
    from makisu_tpu.utils import fileio
    target = tmp_path / "out.json"
    with pytest.raises(ValueError):
        # A circular structure fails mid-serialization — after the
        # temp file opened.
        circular: list = []
        circular.append(circular)
        fileio.write_json_atomic(str(target), circular)
    assert not target.exists()
    assert [p for p in os.listdir(tmp_path)
            if p.endswith(".tmp")] == []


# -- end-to-end residency through the CLI -----------------------------------


def _make_ctx(tmp_path):
    ctx = tmp_path / "ctx"
    (ctx / "src").mkdir(parents=True)
    (ctx / "Dockerfile").write_text(
        "FROM scratch\nCOPY src/ /src/\nCOPY top.txt /top.txt\n")
    for i in range(4):
        (ctx / "src" / f"m{i}.py").write_text(f"# {i}\n" + "x=1\n" * 50)
    (ctx / "top.txt").write_text("top")
    (tmp_path / "root").mkdir()
    return ctx


def _build(tmp_path, ctx, tag, storage="storage"):
    code = cli.main([
        "--log-level", "error", "build", str(ctx), "-t", tag,
        "--hasher", "cpu", "--storage", str(tmp_path / storage),
        "--root", str(tmp_path / "root")])
    assert code == 0
    with ImageStore(str(tmp_path / storage)) as store:
        manifest = store.manifests.load(ImageName.parse(tag))
        return [l.digest.hex() for l in manifest.layers]


def test_cli_builds_reuse_session_and_digests_match(tmp_path):
    ctx = _make_ctx(tmp_path)
    d1 = _build(tmp_path, ctx, "s/t:1")
    d2 = _build(tmp_path, ctx, "s/t:2")
    assert d1 == d2
    session = session_mod.manager().peek(str(ctx))
    assert session is not None
    assert session.builds == 2
    assert session.hits >= 1
    # A fully cached rebuild applies no layer, so memoizes none; one
    # with its last layer edited applies (and memoizes) the first.
    assert not session.layer_replay
    (ctx / "top.txt").write_text("top, edited")
    d3 = _build(tmp_path, ctx, "s/t:3")
    assert d3[0] == d1[0] and d3[1] != d1[1]
    assert len(session.layer_replay) == 1
    assert _build(tmp_path, ctx, "s/t:4") == d3
    assert session.hits >= 3


def test_cli_session_disabled_env(tmp_path, monkeypatch):
    monkeypatch.setenv("MAKISU_TPU_SESSION", "0")
    ctx = _make_ctx(tmp_path)
    _build(tmp_path, ctx, "s/off:1")
    assert session_mod.manager().peek(str(ctx)) is None


# -- worker endpoints -------------------------------------------------------


@pytest.fixture
def worker(tmp_path):
    server = WorkerServer(str(tmp_path / "worker.sock"))
    thread = server.serve_background()
    yield server
    server.shutdown()
    server.server_close()
    thread.join(timeout=5)


def test_worker_sessions_endpoint_and_invalidate(tmp_path, worker):
    ctx = _make_ctx(tmp_path)
    client = WorkerClient(worker.socket_path)
    code = client.build([
        "build", str(ctx), "-t", "w/s:1",
        "--storage", str(tmp_path / "storage"),
        "--root", str(tmp_path / "root")])
    assert code == 0
    sessions = client.sessions()
    assert sessions["count"] == 1
    assert sessions["sessions"][0]["context"] == str(ctx)
    health = client.healthz()
    assert health.sessions["count"] == 1
    assert isinstance(health.session_resident_bytes, int)
    # Second build reuses the session; /healthz hits grow.
    assert client.build([
        "build", str(ctx), "-t", "w/s:2",
        "--storage", str(tmp_path / "storage"),
        "--root", str(tmp_path / "root")]) == 0
    assert client.healthz().sessions["hits"] >= 1
    assert client.invalidate_sessions(str(ctx)) == 1
    assert client.sessions()["count"] == 0
    health = client.healthz()
    assert health.sessions["invalidations"].get("explicit") == 1


def test_worker_metrics_count_unread_cached_layers(tmp_path, worker):
    """``/metrics`` serves ``makisu_layer_replay_total{result=
    "unread"}``: two more a build of an unchanged two-layer context,
    none for a build whose last layer was edited."""
    import re

    ctx = _make_ctx(tmp_path)
    client = WorkerClient(worker.socket_path)

    def unread_after_build(n):
        assert client.build([
            "build", str(ctx), "-t", f"w/unread:{n}",
            "--storage", str(tmp_path / "storage"),
            "--root", str(tmp_path / "root")]) == 0
        found = re.search(
            r'^makisu_layer_replay_total\{result="unread"\} (\S+)$',
            client.metrics(), re.M)
        return float(found.group(1)) if found else 0.0

    # The registry is the process's: count from where it stands.
    base = unread_after_build(1)
    assert unread_after_build(2) == base + 2
    assert unread_after_build(3) == base + 4
    (ctx / "top.txt").write_text("top, edited")
    assert unread_after_build(4) == base + 4


# -- the watcher's descent, by both routes a directory is read by -----------


def _watched_tree(tmp_path):
    root = tmp_path / "tree"
    for sub in ("a/deep/deeper", "b", "skip/inner", "c"):
        (root / sub).mkdir(parents=True)
    (root / "a" / "f.txt").write_text("f")
    os.symlink("a", root / "link-to-a")
    os.mkfifo(root / "b" / "fifo")
    return root


@pytest.mark.parametrize("case", ["whole", "blacklist", "watch-cap"])
def test_watcher_descends_the_same_directories_by_both_routes(
        tmp_path, monkeypatch, case):
    """Directories themselves, never a link to one or a file; what the
    blacklist names is neither listed nor entered; one past the cap is
    where the descent stops (the caller sees more than the cap and
    falls back)."""
    from makisu_tpu import native
    root = _watched_tree(tmp_path)
    blacklist = [str(root / "skip")] if case == "blacklist" else []
    if case == "watch-cap":
        monkeypatch.setenv("MAKISU_TPU_SESSION_MAX_WATCHES", "3")
    watcher = object.__new__(session_mod.InotifyWatcher)
    watcher.root, watcher.blacklist = str(root), blacklist
    if native.dir_reader() is None:
        pytest.skip("libdirscan.so cannot be built or loaded here")
    by_native = watcher._dirs()
    with monkeypatch.context() as m:
        m.setattr(native, "dir_reader", lambda: None)
        by_python = watcher._dirs()
    assert by_native == by_python
    everything = {str(root)} | {str(root / p) for p in (
        "a", "a/deep", "a/deep/deeper", "b", "skip", "skip/inner", "c")}
    if case == "whole":
        assert set(by_native) == everything and by_native[0] == str(root)
    elif case == "blacklist":
        assert set(by_native) == everything - {
            str(root / "skip"), str(root / "skip" / "inner")}
    else:
        assert len(by_native) == 4 and set(by_native) <= everything


def test_watcher_is_armed_before_the_listings_first_stat(
        tmp_path, monkeypatch, dir_route):
    """The order the guarantee "a layer holds the tree's files as they
    are on disk when the build starts" rests on: the session's watches
    are placed (its own descent asks type bits, never the listing)
    before the build's listing takes its first ``lstat``."""
    ctx = _make_ctx(tmp_path)
    order = []
    real_add, real_start = (session_mod.InotifyWatcher._add_watches,
                            walk_mod.TreeListing._start)

    def add_watches(self):
        ok = real_add(self)
        order.append(("armed", time.time_ns()))
        return ok

    def start(self):
        if self.started_ns is None:
            real_start(self)
            order.append(("first-stat", self.started_ns))

    monkeypatch.setattr(session_mod.InotifyWatcher, "_add_watches",
                        add_watches)
    monkeypatch.setattr(walk_mod.TreeListing, "_start", start)
    _build(tmp_path, ctx, "order/t:1")
    kinds = [kind for kind, _ in order]
    assert kinds[0] == "armed" and "first-stat" in kinds
    assert order[0][1] <= order[kinds.index("first-stat")][1]
    s = session_mod.manager().peek(str(ctx))
    if s.watcher is not None and s.watcher.healthy:
        assert sorted(s.watcher._wd_paths.values()) \
            == [str(ctx), str(ctx / "src")]
