"""CAS / manifest / image store tests (reference strategy:
lib/storage/*_test.go incl. concurrency stress)."""

import os
import threading

import pytest

from makisu_tpu.docker.image import (
    Descriptor,
    Digest,
    DistributionManifest,
    ImageName,
)
from makisu_tpu.storage import CASStore, ImageStore, ManifestStore


def test_cas_roundtrip(tmp_path):
    store = CASStore(str(tmp_path / "cas"))
    store.write_bytes("abcd1234", b"hello")
    assert store.exists("abcd1234")
    assert store.size("abcd1234") == 5
    with store.open("abcd1234") as f:
        assert f.read() == b"hello"


def test_cas_sharding_and_reload(tmp_path):
    root = str(tmp_path / "cas")
    CASStore(root).write_bytes("ffab99", b"x")
    assert os.path.isfile(os.path.join(root, "ff", "ffab99"))
    # A new instance over the same root sees existing entries.
    assert CASStore(root).exists("ffab99")


def test_cas_first_writer_wins(tmp_path):
    store = CASStore(str(tmp_path / "cas"))
    store.write_bytes("k1", b"first")
    store.write_bytes("k1", b"second")
    with store.open("k1") as f:
        assert f.read() == b"first"


def test_cas_link_in_out(tmp_path):
    store = CASStore(str(tmp_path / "cas"))
    src = tmp_path / "src.bin"
    src.write_bytes(b"payload")
    store.link_file("deadbeef", str(src))
    dst = tmp_path / "out" / "copy.bin"
    store.link_out("deadbeef", str(dst))
    assert dst.read_bytes() == b"payload"


def test_cas_lru_eviction(tmp_path):
    store = CASStore(str(tmp_path / "cas"), max_entries=3)
    for i in range(5):
        store.write_bytes(f"k{i}", bytes([i]))
        store._last_access[f"k{i}"] = float(i)  # deterministic order
        with store._lock:
            store._evict_locked()
    keys = set(store.keys())
    assert len(keys) == 3
    assert "k4" in keys and "k0" not in keys


def test_cas_concurrent_writers(tmp_path):
    store = CASStore(str(tmp_path / "cas"))
    errors = []

    def work(i):
        try:
            for j in range(20):
                store.write_bytes(f"key{j}", b"v" * (j + 1))
                assert store.exists(f"key{j}")
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert len(store.keys()) == 20


def _manifest(n: int) -> DistributionManifest:
    return DistributionManifest(
        config=Descriptor("c", n, Digest.from_hex("0" * 64)), layers=[])


def test_manifest_store(tmp_path):
    ms = ManifestStore(str(tmp_path / "m"))
    name = ImageName("reg.io", "team/app", "v1")
    ms.save(name, _manifest(1))
    assert ms.exists(name)
    assert ms.load(name).config.size == 1
    ms.delete(name)
    assert not ms.exists(name)


def test_image_store_sandbox_cleanup(tmp_path):
    with ImageStore(str(tmp_path / "store")) as store:
        sandbox = store.sandbox_dir
        assert os.path.isdir(sandbox)
        open(os.path.join(sandbox, "scratch"), "w").close()
    assert not os.path.exists(sandbox)


# -- bulk ingest (PR 25) ------------------------------------------------------

def _entries(n: int, size: int = 300) -> list[tuple[str, bytes]]:
    """``n`` content-addressed entries: the name is sha256 of the bytes."""
    import hashlib
    out = []
    for i in range(n):
        data = (b"%06d" % i) * (size // 6)
        out.append((hashlib.sha256(data).hexdigest(), data))
    return out


def _stored(entries) -> dict[str, tuple[int, bytes]]:
    """The tree ``entries`` make: mode 0600 files, an empty ``_tmp/``."""
    tree = {os.path.join(name[:2], name): (0o600, data)
            for name, data in entries}
    tree["_tmp/"] = (0, b"")
    return tree


def test_cas_write_many_four_calls_an_entry_none_under_lock(
        tmp_path, fs_calls, store_tree):
    store = CASStore(str(tmp_path / "cas"))
    entries = _entries(40)
    shards = {name[:2] for name, _ in entries}
    rec = fs_calls(store)
    store.write_many(entries)
    assert rec.calls["open"] == rec.calls["write"] == 40
    assert rec.calls["close"] == rec.calls["rename"] == 40
    assert rec.calls["mkdir"] == len(shards)      # once a shard
    assert rec.total() == 4 * 40 + len(shards)    # and nothing else
    assert rec.under_lock == []
    assert store_tree(store.root) == _stored(entries)
    # A second store over the same root has seen the shards: no mkdir.
    again = CASStore(store.root)
    more = [e for e in _entries(400) if e[0][:2] in shards][:5]
    rec = fs_calls(again)
    again.write_many(more)
    assert rec.calls["mkdir"] == 0 and rec.total() == 4 * len(more)


@pytest.mark.parametrize("ingest", ["write_bytes", "link_file"])
def test_cas_named_ingest_keeps_one_stat_and_no_lock(
        tmp_path, fs_calls, ingest):
    """The arbitrary-name paths share the staging sequence: one stat of
    the final path (first writer wins), no tempfile, fdopen, makedirs
    or probe of the staging name."""
    store = CASStore(str(tmp_path / "cas"))
    src = tmp_path / "src.bin"
    src.write_bytes(b"payload")
    rec = fs_calls(store)
    if ingest == "write_bytes":
        store.write_bytes("abcd", b"payload")
        expect = {"open": 1, "write": 1, "close": 1, "isfile": 1,
                  "mkdir": 1, "rename": 1}
    else:
        store.link_file("abcd", str(src))
        expect = {"link": 1, "isfile": 1, "mkdir": 1, "rename": 1}
    assert dict(rec.calls) == expect
    assert rec.under_lock == []
    assert os.listdir(store._tmp_dir) == []
    with store.open("abcd") as f:
        assert f.read() == b"payload"
    # The loser of first-writer-wins removes its staging file.
    store.write_bytes("abcd", b"other")
    assert os.listdir(store._tmp_dir) == []
    with store.open("abcd") as f:
        assert f.read() == b"payload"


def test_cas_queries_never_stat_under_the_lock(tmp_path, fs_calls):
    store = CASStore(str(tmp_path / "cas"))
    store.write_bytes("abcd", b"payload")
    rec = fs_calls(store)
    assert store.exists("abcd") and not store.exists("ffff")
    assert store.size("abcd") == 7
    assert store.path("abcd").endswith("abcd")
    store.delete("abcd")
    assert rec.total() == 5 and rec.under_lock == []
    assert "abcd" not in store._last_access


def test_cas_write_many_overlapping_threads(tmp_path, store_tree):
    """Two threads ingest overlapping names: each name ends up stored
    once, whole, and nothing is left in staging."""
    import sys
    store = CASStore(str(tmp_path / "cas"))
    entries = _entries(120, size=6000)
    halves = [entries[:80], entries[40:]]
    barrier = threading.Barrier(2)
    errors = []

    def work(mine):
        try:
            barrier.wait(timeout=10)
            for i in range(0, len(mine), 8):
                store.write_many(mine[i:i + 8])
        except Exception as e:  # pragma: no cover
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(h,)) for h in halves]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not errors and not any(t.is_alive() for t in threads)
    assert store_tree(store.root) == _stored(entries)
    assert set(store._last_access) == {name for name, _ in entries}


def test_cas_write_many_failure_leaves_no_partial_entry(
        tmp_path, fs_calls, store_tree):
    import errno
    store = CASStore(str(tmp_path / "cas"))
    entries = _entries(10)
    rec = fs_calls(store)
    rec.fail_at["write"] = (4, OSError(errno.ENOSPC, "no space"))
    with pytest.raises(OSError):
        store.write_many(entries)
    # Three committed and recorded, the fourth gone without a trace.
    assert store_tree(store.root) == _stored(entries[:3])
    assert sorted(store._last_access) == sorted(n for n, _ in entries[:3])


def test_cas_rename_remakes_a_shard_that_went_away(tmp_path):
    import shutil
    store = CASStore(str(tmp_path / "cas"))
    (name, data), (name2, data2) = _entries(2)
    store.write_many([(name, data)])
    shutil.rmtree(os.path.join(store.root, name[:2]))
    store.write_many([(name, data)])
    assert store.exists(name)


def test_cas_write_many_holds_the_entry_cap_and_pins(tmp_path):
    store = CASStore(str(tmp_path / "cas"), max_entries=8)
    entries = _entries(20)
    pinned = entries[0][0]
    store.pin_check = lambda name: name == pinned
    store.write_many(entries[:4])
    for i, (name, _) in enumerate(entries[:4]):
        store._last_access[name] = float(i)      # the pinned one oldest
    store.write_many(entries[4:])
    keys = set(store.keys())
    assert len(keys) == 8 and pinned in keys
    assert set(store._last_access) == keys
    assert not keys & {name for name, _ in entries[1:4]}
