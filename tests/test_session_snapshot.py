"""Chunk-addressed session snapshots: checkpoint on finish_build,
restore on cold acquire with the full invalidation story
(flag_identity / isa_change / staleness), digest byte-identity under a
deliberately stale restored stat cache, the scan-memo LRU discipline,
the lru_restore eviction label, the worker snapshot endpoints, and the
census accounting for snapshot recipes."""

import importlib
import json
import os
import time

import pytest

from conftest import cas_entry_path

from makisu_tpu import cli
from makisu_tpu.cache.census import StorageCensus
from makisu_tpu.docker.image import ImageName
from makisu_tpu.storage import ImageStore
from makisu_tpu.worker import WorkerClient, WorkerServer
from makisu_tpu.worker import session as session_mod
from makisu_tpu.worker import snapshots as snapshots_mod

walk_mod = importlib.import_module("makisu_tpu.snapshot.walk")


@pytest.fixture(autouse=True)
def _fresh_sessions(monkeypatch):
    """Empty process-global session registry, window-0 racy discipline
    (snapshots certify immediately), and the snapshot plane forced ON
    (one-shot CLI builds are not resident, so the auto policy would
    skip the checkpoint these tests assert on)."""
    monkeypatch.setenv("MAKISU_TPU_STAT_CACHE_WINDOW_NS", "0")
    monkeypatch.setenv("MAKISU_TPU_SESSION_SNAPSHOT", "1")
    session_mod.manager().reset()
    yield
    session_mod.manager().reset()


def _make_ctx(tmp_path, name="ctx"):
    ctx = tmp_path / name
    (ctx / "src").mkdir(parents=True)
    (ctx / "Dockerfile").write_text(
        "FROM scratch\nCOPY src/ /src/\nCOPY top.txt /top.txt\n")
    for i in range(4):
        (ctx / "src" / f"m{i}.py").write_text(f"# {i}\n" + "x=1\n" * 50)
    (ctx / "top.txt").write_text("top")
    (tmp_path / "root").mkdir(exist_ok=True)
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


def _recipes(tmp_path, storage="storage"):
    snapdir = tmp_path / storage / "serve" / "snapshots"
    if not snapdir.is_dir():
        return []
    return [json.loads((snapdir / n).read_text())
            for n in sorted(os.listdir(snapdir))
            if n.endswith(".json")]


# -- scan-memo LRU (the aging fix) ------------------------------------------


def test_scan_memo_trim_is_recency_ordered(tmp_path, monkeypatch):
    """A hot key replayed every build survives a burst of one-shot
    keys that arrived after it: lookups bump recency, and the trim
    evicts the least recently stored-or-replayed key."""
    monkeypatch.setattr(session_mod, "_SCAN_MEMO_KEEP", 4)
    s = session_mod.BuildSession(str(tmp_path), "id")
    for i in range(4):
        s.scan_store(f"src{i}", i, i, 1, 1)
    # Replay the oldest-inserted key: it must move to the young end.
    assert s.scan_lookup("src0", 0) is not None
    s.scan_store("src4", 4, 4, 1, 1)
    assert len(s.scan_memo) == 4
    assert s.scan_lookup("src0", 0) is not None   # hot key survived
    assert s.scan_lookup("src1", 1) is None       # stale one aged out


# -- checkpoint + restore round trip ----------------------------------------


def test_finish_build_checkpoints_and_cold_acquire_restores(tmp_path):
    ctx = _make_ctx(tmp_path)
    d1 = _build(tmp_path, ctx, "snap/t:1")
    d2 = _build(tmp_path, ctx, "snap/t:2")
    assert d1 == d2
    recipes = _recipes(tmp_path)
    assert len(recipes) == 1
    recipe = recipes[0]
    assert recipe["schema"] == snapshots_mod.SNAPSHOT_SCHEMA
    assert recipe["context"] == os.path.realpath(str(ctx))
    assert "scan" in recipe["shards"]
    mgr = session_mod.manager()
    assert mgr.snapshot_counts.get("write", 0) == 2

    # The kill -9 model: every resident session dies with the process;
    # only the checkpoint survives.
    mgr.reset()
    d3 = _build(tmp_path, ctx, "snap/t:3")
    assert d3 == d1
    assert mgr.snapshot_counts.get("restore", 0) == 1
    session = mgr.peek(str(ctx))
    assert session is not None
    assert session.builds >= 3   # build count carried by the recipe


def test_restore_refused_on_flag_identity_change(tmp_path):
    ctx = _make_ctx(tmp_path)
    _build(tmp_path, ctx, "snap/fi:1")
    mgr = session_mod.manager()
    mgr.reset()
    storage = str(tmp_path / "storage")
    s, verdict = mgr.acquire(str(ctx), "other-identity",
                             restore_spec=(storage,
                                           "other-portable-identity"))
    assert verdict == "miss"   # cold create, never a silent replay
    assert mgr.snapshot_counts.get("restore_refused", 0) == 1
    assert mgr.last_restore_failure["reason"] == "flag_identity"
    mgr.release(s)


def test_restore_refused_on_isa_change(tmp_path, monkeypatch):
    ctx = _make_ctx(tmp_path)
    _build(tmp_path, ctx, "snap/isa:1")
    (recipe,) = _recipes(tmp_path)
    mgr = session_mod.manager()
    mgr.reset()
    monkeypatch.setattr(session_mod, "_isa_identity",
                        lambda: "avx512-migrated-elsewhere")
    storage = str(tmp_path / "storage")
    s, verdict = mgr.acquire(str(ctx), "id",
                             restore_spec=(storage,
                                           recipe["portable_identity"]))
    assert verdict == "miss"
    assert mgr.last_restore_failure["reason"] == "isa_change"
    mgr.release(s)


def test_restore_refused_on_stale_snapshot(tmp_path, monkeypatch):
    ctx = _make_ctx(tmp_path)
    _build(tmp_path, ctx, "snap/ttl:1")
    (recipe,) = _recipes(tmp_path)
    mgr = session_mod.manager()
    mgr.reset()
    monkeypatch.setenv("MAKISU_TPU_SESSION_TTL", "0")
    time.sleep(0.01)
    storage = str(tmp_path / "storage")
    s, verdict = mgr.acquire(str(ctx), "id",
                             restore_spec=(storage,
                                           recipe["portable_identity"]))
    assert verdict == "miss"
    assert mgr.last_restore_failure["reason"] == "stale"
    mgr.release(s)


# -- digest integrity under a stale restored stat cache ---------------------


def test_stale_restored_stat_cache_never_replays(tmp_path):
    """Edit a file between checkpoint and restore with its size AND
    mtime preserved (the adversarial racily-clean shape). The restored
    stat/content-ID entries must re-hash instead of replaying — the
    rebuild's digests must match a cold oracle build of the edited
    tree, not the snapshot-era content."""
    ctx = _make_ctx(tmp_path)
    d1 = _build(tmp_path, ctx, "snap/stale:1")
    target = ctx / "src" / "m0.py"
    st = target.stat()
    body = target.read_text()
    assert "x=1" in body
    edited = body.replace("x=1", "x=9", 1)   # same byte length
    target.write_text(edited)
    os.utime(target, ns=(st.st_atime_ns, st.st_mtime_ns))
    assert target.stat().st_mtime_ns == st.st_mtime_ns

    mgr = session_mod.manager()
    mgr.reset()
    d2 = _build(tmp_path, ctx, "snap/stale:2")
    assert d2 != d1   # the edit is in the image, not the stale memo

    # Cold oracle over fresh storage (no snapshot exists there): the
    # restored rebuild must be byte-identical to it.
    mgr.reset()
    d3 = _build(tmp_path, ctx, "snap/stale:oracle", storage="oracle")
    assert d2 == d3


# -- eviction labeling ------------------------------------------------------


def test_restore_eviction_labels_lru_restore(tmp_path, monkeypatch):
    ctx_a = _make_ctx(tmp_path, "ctxa")
    ctx_b = _make_ctx(tmp_path, "ctxb")
    _build(tmp_path, ctx_a, "snap/a:1")
    _build(tmp_path, ctx_b, "snap/b:1")
    mgr = session_mod.manager()
    mgr.reset()
    monkeypatch.setenv("MAKISU_TPU_SESSION_MAX", "1")
    _build(tmp_path, ctx_a, "snap/a:2")   # restored; 1 resident
    _build(tmp_path, ctx_b, "snap/b:2")   # restored; evicts ctx_a
    assert mgr.snapshot_counts.get("restore", 0) == 2
    assert mgr.invalidations.get("lru_restore") == 1
    assert "lru" not in mgr.invalidations


# -- worker endpoints -------------------------------------------------------


@pytest.fixture
def worker(tmp_path):
    server = WorkerServer(str(tmp_path / "worker.sock"))
    thread = server.serve_background()
    yield server
    server.shutdown()
    server.server_close()
    thread.join(timeout=5)


def test_worker_snapshot_endpoints(tmp_path, worker):
    ctx = _make_ctx(tmp_path)
    client = WorkerClient(worker.socket_path)
    assert client.build([
        "--log-level", "error", "build", str(ctx), "-t", "w/snap:1",
        "--hasher", "cpu",
        "--storage", str(tmp_path / "storage"),
        "--root", str(tmp_path / "root")]) == 0
    # Forced checkpoint of every session, then the recipe pull the
    # fleet prewarm path uses.
    assert client.snapshot_sessions("")["snapshotted"] == 1
    recipe = client.session_snapshot(str(ctx))
    assert recipe["schema"] == snapshots_mod.SNAPSHOT_SCHEMA
    assert recipe["context"] == os.path.realpath(str(ctx))
    # Staging a restore from the local recipe succeeds (all chunks are
    # already local); refusals come back as data, not errors.
    resp = client.restore_session({"context": str(ctx)})
    assert resp["ok"] is True
    bogus = client.restore_session({"context": str(tmp_path / "nope")})
    assert bogus["ok"] is False and bogus["reason"] == "no_snapshot"
    sessions = client.sessions()
    assert sessions["snapshot"]["write"] >= 1


# -- census accounting ------------------------------------------------------


def test_census_accounts_snapshots_and_flags_orphans(tmp_path):
    ctx = _make_ctx(tmp_path)
    _build(tmp_path, ctx, "snap/census:1")
    storage = str(tmp_path / "storage")
    (recipe,) = _recipes(tmp_path)

    out = StorageCensus(storage).census()
    chunks_plane = out["planes"]["chunks"]
    assert chunks_plane["snapshots"] == 1
    assert chunks_plane["snapshot_bytes"] > 0

    audit = StorageCensus(storage).audit()
    snaps = audit["classification"]["snapshots"]
    assert snaps == {"live": 1, "orphaned": 0, "orphaned_bytes": 0,
                     "dangling": 0}

    # Delete one shard chunk: the recipe classifies as orphaned with a
    # warning finding — never a crash.
    victim = recipe["shards"]["scan"]["chunk"]
    os.unlink(cas_entry_path(os.path.join(storage, "chunks"), victim))
    audit = StorageCensus(storage).audit()
    snaps = audit["classification"]["snapshots"]
    assert snaps["orphaned"] == 1 and snaps["live"] == 0
    kinds = {f["kind"] for f in audit["findings"]}
    assert "orphaned_snapshot" in kinds


# -- the checkpoint's baseline out of the build's listing -------------------


def _walk_sigs(tmp_path, recipe, storage="storage"):
    """The recipe's persisted walk baseline, all shards."""
    chunks = snapshots_mod.SnapshotStore(
        str(tmp_path / storage)).chunk_store()
    sigs = {}
    for b in range(snapshots_mod.WALK_BUCKETS):
        sigs.update(snapshots_mod._load_shard(chunks, recipe,
                                              f"walk/{b}") or {})
    return sigs


@pytest.fixture
def watched(tmp_path):
    """A context whose sessions get a healthy inotify watcher (only
    those walk a persistence baseline at their first checkpoint)."""
    ctx = _make_ctx(tmp_path)
    probe = session_mod.InotifyWatcher(str(ctx), [])
    healthy = probe.healthy
    probe.close()
    if not healthy:
        pytest.skip("inotify unavailable on this host")
    return ctx


def test_first_checkpoint_replays_the_builds_listing(tmp_path, watched,
                                                     monkeypatch):
    """The baseline a first build's checkpoint persists holds what a
    live ``snapshot_tree`` at that moment holds, took its stats from the
    build's listing, and certifies against the moment before the first
    of them; a cold acquire restores it to ``exact`` as before."""
    ctx = watched
    stat_times, taken = [], []
    real_list_dir = walk_mod._list_dir
    real_lstat = walk_mod.TreeListing.lstat
    real_snapshot_tree = walk_mod.snapshot_tree

    def list_dir(path):
        out = real_list_dir(path)
        if path.startswith(str(ctx)) and not taken:
            stat_times.append(time.time_ns())
        return out

    def lstat(self, path):
        st = real_lstat(self, path)
        stat_times.append(time.time_ns())
        return st

    def snapshot_tree(root, blacklist=None, listing=None):
        live = real_snapshot_tree(root, blacklist)
        reads_before = len(stat_times)
        taken.append((real_snapshot_tree(root, blacklist, listing),
                      live, listing))
        # src/ and top.txt came out of the listing: only the context's
        # root was left to list.
        assert len(stat_times) - reads_before <= 2
        return taken[-1][0]

    monkeypatch.setattr(walk_mod, "_list_dir", list_dir)
    monkeypatch.setattr(walk_mod.TreeListing, "lstat", lstat)
    monkeypatch.setattr(walk_mod, "snapshot_tree", snapshot_tree)
    began = time.time_ns()
    d1 = _build(tmp_path, ctx, "snap/listing:1")
    [(snap, live, listing)] = taken
    assert listing is not None and listing.started_ns is not None
    assert snap.sigs == live.sigs and len(snap.sigs) == 7
    assert began <= snap.captured_ns == listing.started_ns \
        <= min(stat_times) < live.captured_ns
    (recipe,) = _recipes(tmp_path)
    assert recipe["exact"] is True
    assert recipe["walk"]["captured_ns"] == snap.captured_ns
    assert _walk_sigs(tmp_path, recipe) \
        == {p: list(sig) for p, sig in live.sigs.items()}
    mgr = session_mod.manager()
    assert mgr.peek(str(ctx)).build_listing is None

    mgr.reset()
    assert _build(tmp_path, ctx, "snap/listing:2") == d1
    assert mgr.snapshot_counts.get("restore", 0) == 1
    assert mgr.peek(str(ctx)).exact is True
    # The restore's gap delta, at begin_build, walks live: the new
    # build's listing has nothing yet, and is not handed in.
    assert [row[2] for row in taken[1:]] == [None]


def test_touch_after_the_scan_is_dirty_or_fresh_in_the_recipe(
        tmp_path, watched, monkeypatch):
    """The checkpoint replays stats taken before the touch: the path
    must come back dirty, from the watcher's event or from ``fresh``
    (certified against the listing's start, not the checkpoint's)."""
    from makisu_tpu.builder import BuildPlan
    ctx = watched
    victim = ctx / "src" / "m1.py"
    real_execute = BuildPlan.execute

    def execute(self):
        manifest = real_execute(self)
        victim.write_text("# touched after the scan\n")
        return manifest

    monkeypatch.setattr(BuildPlan, "execute", execute)
    _build(tmp_path, ctx, "snap/touch:1")
    (recipe,) = _recipes(tmp_path)
    assert str(victim) in recipe["pending_dirty"] \
        or str(victim) in recipe["walk"]["fresh"]
    # The persisted signature is the scanned file's, so a restore's
    # delta finds the touch as well.
    assert _walk_sigs(tmp_path, recipe)[str(victim)] \
        != list(walk_mod.stat_signature(os.lstat(victim)))


@pytest.fixture(scope="module")
def small_files_builds(tmp_path_factory):
    """A ``small-files``-shaped tree (200 files, 40 directories, one
    ``COPY``) built with the build's listing and with it closed from
    the start, where every pass gets what ``listing=None`` gets."""
    from makisu_tpu.builder import BuildPlan
    from makisu_tpu.cache import NoopCacheManager
    from makisu_tpu.context import BuildContext
    from makisu_tpu.dockerfile import parse_file

    base = tmp_path_factory.mktemp("listing-equality")
    ctx_dir = base / "ctx"
    for d in range(40):
        (ctx_dir / "app" / f"pkg{d % 8}" / f"d{d}").mkdir(parents=True)
    for i in range(200):
        d = i % 40
        body = (f"module {i}\n" * (1 + 37 * i % 900)).encode()
        (ctx_dir / "app" / f"pkg{d % 8}" / f"d{d}"
         / f"f{i}.js").write_bytes(body)
    os.symlink("pkg0", ctx_dir / "app" / "current")
    then = time.time() - 100
    for parent, dirs, files in os.walk(ctx_dir):
        for name in files + [parent]:
            os.utime(os.path.join(parent, name), (then, then))
    dockerfile = "FROM scratch\nCOPY app /app/\nCOPY app/pkg1 /one/\n"

    def build(name, with_listing):
        root = base / f"root-{name}"
        root.mkdir()
        store = ImageStore(str(base / f"store-{name}"))
        ctx = BuildContext(str(root), str(ctx_dir), store, sync_wait=0.0)
        if not with_listing:
            ctx.listing.close()
        plan = BuildPlan(ctx, ImageName("", "eq/app", name), [],
                         NoopCacheManager(), parse_file(dockerfile),
                         allow_modify_fs=False, force_commit=True)
        manifest = plan.execute()
        config = json.loads(store.layers.open(
            manifest.config.digest.hex()).read())
        return {
            "cache_ids": [node.cache_id for stage in plan.stages
                          for node in stage.nodes],
            "tar_digests": config["rootfs"]["diff_ids"],
            "gzip_digests": [str(l.digest) for l in manifest.layers],
            "served": ctx.listing.serves(str(ctx_dir)),
        }

    return build("with", True), build("without", False)


@pytest.mark.parametrize("what",
                         ["cache_ids", "tar_digests", "gzip_digests"])
def test_listing_changes_no_identity(small_files_builds, what):
    with_listing, without = small_files_builds
    assert with_listing["served"] and not without["served"]
    assert len(with_listing[what]) >= 2
    assert with_listing[what] == without[what]
