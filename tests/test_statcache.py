"""Stat-keyed content-ID cache: warm builds skip re-reading unchanged
context files without ever changing cache identity."""

import os
import threading
import time
import types
import zlib

import pytest

from makisu_tpu.builder import BuildPlan
from makisu_tpu.cache import CacheManager, MemoryStore, NoopCacheManager
from makisu_tpu.context import BuildContext
from makisu_tpu.docker.image import ImageName
from makisu_tpu.dockerfile import parse_file
from makisu_tpu.steps.add_copy import AddCopyStep
from makisu_tpu.storage import ImageStore
from makisu_tpu.utils.statcache import ContentIDCache


def _build(tmp_path, tag, store_name="store", kv=None):
    ctx_dir = tmp_path / "ctx"
    root = tmp_path / f"root-{tag}"
    root.mkdir()
    store = ImageStore(str(tmp_path / store_name))
    ctx = BuildContext(str(root), str(ctx_dir), store, sync_wait=0.0)
    mgr = (CacheManager(kv, store) if kv is not None
           else NoopCacheManager())
    plan = BuildPlan(ctx, ImageName("", "t/statcache", tag), [], mgr,
                     parse_file("FROM scratch\nCOPY . /app/\n"),
                     allow_modify_fs=False, force_commit=True)
    manifest = plan.execute()
    mgr.wait_for_push()
    cache_ids = [s.nodes[-1].step.cache_id for s in plan.stages]
    return manifest, cache_ids


def _fake_stat(size=3, ino=7, dev=11, age_s=10.0):
    now = time.time_ns()
    t = now - int(age_s * 1e9)
    return types.SimpleNamespace(st_size=size, st_mtime_ns=t,
                                 st_ctime_ns=t, st_ino=ino, st_dev=dev)


def test_warm_build_skips_unchanged_file_reads(tmp_path, monkeypatch):
    # Window 0: the files were just written, and this test pins the
    # skip-reads behavior, not the racily-clean guard (tested below).
    monkeypatch.setenv("MAKISU_TPU_STAT_CACHE_WINDOW_NS", "0")
    ctx_dir = tmp_path / "ctx"
    ctx_dir.mkdir()
    for i in range(20):
        (ctx_dir / f"f{i}.bin").write_bytes(os.urandom(3000))
    m1, ids1 = _build(tmp_path, "a")
    assert (tmp_path / "store" / "content_id_cache.json").exists()

    # Second build: same store -> the cache is primed. Count file
    # opens under the context dir during the checksum pass alone
    # (AddCopyStep._checksum_tree): the commit reads every file it
    # tars, on the tar writer's thread and its read-ahead workers.
    opened = []
    real_open = open
    in_checksum = threading.local()

    def counting_open(path, *a, **k):
        if (getattr(in_checksum, "depth", 0) and isinstance(path, str)
                and str(ctx_dir) in path):
            opened.append(path)
        return real_open(path, *a, **k)

    real_checksum_tree = AddCopyStep._checksum_tree
    walked = []

    def scoped_checksum_tree(self, ctx, path, *a, **k):
        in_checksum.depth = getattr(in_checksum, "depth", 0) + 1
        walked.append(path)
        try:
            return real_checksum_tree(self, ctx, path, *a, **k)
        finally:
            in_checksum.depth -= 1

    import builtins
    monkeypatch.setattr(AddCopyStep, "_checksum_tree",
                        scoped_checksum_tree)
    monkeypatch.setattr(builtins, "open", counting_open)
    m2, ids2 = _build(tmp_path, "b")
    monkeypatch.undo()
    assert ids1 == ids2  # identity unchanged
    assert [str(l.digest) for l in m1.layers] == \
        [str(l.digest) for l in m2.layers]
    # The pass ran, over every file, and opened none of them.
    assert sum(p.endswith(".bin") for p in walked) == 20
    content_reads = [p for p in opened if p.endswith(".bin")]
    assert content_reads == []


def test_content_change_misses_even_with_restored_mtime(tmp_path,
                                                        monkeypatch):
    # Window 0 isolates the ctime mechanism from the racy guard.
    monkeypatch.setenv("MAKISU_TPU_STAT_CACHE_WINDOW_NS", "0")
    ctx_dir = tmp_path / "ctx"
    ctx_dir.mkdir()
    victim = ctx_dir / "v.bin"
    victim.write_bytes(b"A" * 4096)
    _, ids1 = _build(tmp_path, "a")
    st = victim.stat()
    victim.write_bytes(b"B" * 4096)  # same size
    os.utime(victim, ns=(st.st_atime_ns, st.st_mtime_ns))  # spoof mtime
    _, ids2 = _build(tmp_path, "b")
    # ctime changed (utime can't restore it): the cache missed, the
    # file re-hashed, and the COPY step's cache ID moved.
    assert ids1 != ids2


def test_racily_clean_entries_are_not_trusted(tmp_path):
    """A file hashed in the same coarse-timestamp tick it was written
    in could hide a later same-size edit — the default window refuses
    such entries (git's racily-clean rule)."""
    c = ContentIDCache(str(tmp_path / "c.json"))
    st = _fake_stat(age_s=0.0)  # written "now", hashed "now"
    c.put("f", st, 123)
    assert c.get("f", st) is None  # inside the racy window
    old = _fake_stat(age_s=10.0)  # timestamps 10s before the hash
    c.put("g", old, 456)
    assert c.get("g", old) == 456  # safely clean


def test_disabled_switch_preserves_identity(tmp_path, monkeypatch):
    ctx_dir = tmp_path / "ctx"
    ctx_dir.mkdir()
    (ctx_dir / "x.bin").write_bytes(os.urandom(5000))
    _, ids_on = _build(tmp_path, "a")
    monkeypatch.setenv("MAKISU_TPU_STAT_CACHE", "0")
    _, ids_off = _build(tmp_path, "b", store_name="store2")
    # The framed summary is the identity either way: toggling the stat
    # shortcut never invalidates existing caches.
    assert ids_on == ids_off


def test_cache_survives_corrupt_file(tmp_path):
    cache_path = tmp_path / "cache.json"
    cache_path.write_text("{not json")
    c = ContentIDCache(str(cache_path))
    st = _fake_stat()
    assert c.get("a", st) is None
    c.put("a", st, 123)
    c.save()
    c2 = ContentIDCache(str(cache_path))
    assert c2.get("a", st) == 123


def test_stat_key_covers_inode_and_device(tmp_path):
    c = ContentIDCache(str(tmp_path / "c.json"))
    st = _fake_stat(ino=7, dev=11)
    c.put("f", st, zlib.crc32(b"abc"))
    assert c.get("f", st) == zlib.crc32(b"abc")
    # Same rel path, same size/times, different inode: miss.
    assert c.get("f", _fake_stat(ino=8, dev=11)) is None
    # Different device (bind mount / other fs, inode reused): miss.
    assert c.get("f", _fake_stat(ino=7, dev=12)) is None


def test_namespace_scopes_contexts(tmp_path):
    path = str(tmp_path / "c.json")
    a = ContentIDCache(path, namespace="/ctx/a")
    b = ContentIDCache(path, namespace="/ctx/b")
    st = _fake_stat()
    a.put("data.bin", st, 111)
    a.save()
    # b shares the FILE but not the namespace: no cross-context hit.
    b._entries = None  # force reload from disk
    assert b.get("data.bin", st) is None
