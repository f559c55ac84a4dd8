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
from makisu_tpu.storage import cas as cas_mod


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


# -- one owner of the layout (PR 28) ------------------------------------------


def _handle(kind: str, root: str):
    """A live store (root made, recency kept) or the bare directory
    (nothing touched until the first put)."""
    return CASStore(root) if kind == "live" else cas_mod.CASDir(root)


@pytest.mark.parametrize("op", ["put", "read", "delete", "walk"])
@pytest.mark.parametrize("kind", ["live", "offline"])
def test_cas_handle_live_and_offline_agree(tmp_path, store_tree, kind, op):
    """Either handle leaves the tree ``write_many`` leaves and reads it
    back the same way: one layout, whoever holds it."""
    root = str(tmp_path / "cas")
    store = _handle(kind, root)
    entries = _entries(12)
    for name, data in entries:
        store.put(name, data)
    if op == "put":
        assert store_tree(root) == _stored(entries)
        # The other kind of handle over the same root adds to it.
        other = _handle("offline" if kind == "live" else "live", root)
        more = _entries(15)[12:]
        for name, data in more:
            other.put(name, data)
        assert store_tree(root) == _stored(entries + more)
    elif op == "read":
        for name, data in entries:
            assert store.read(name) == data
            with store.open(name) as f:
                assert f.read() == data
        with pytest.raises(FileNotFoundError):
            store.open("0" * 64)
    elif op == "delete":
        gone, kept = entries[:5], entries[5:]
        for name, _ in gone:
            store.delete(name)
        store.delete(gone[0][0])                 # absent: not an error
        tree = store_tree(root)
        assert {k: v for k, v in tree.items() if not k.endswith("/")} \
            == {k: v for k, v in _stored(kept).items()
                if not k.endswith("/")}
        assert sorted(store.keys()) == sorted(n for n, _ in kept)
    else:
        rows = sorted(store.walk())
        assert [n for n, _, _ in rows] == sorted(store.keys())
        assert [(n, s) for n, s, _ in rows] == sorted(
            (n, len(d)) for n, d in entries)
        assert all(mtime > 0 for _, _, mtime in rows)


@pytest.mark.parametrize("hazard", ["missing_root", "stray_staging",
                                    "shard_removed", "entry_vanishes"])
@pytest.mark.parametrize("kind", ["live", "offline"])
def test_cas_walk_yields_through_what_a_census_meets(tmp_path, kind,
                                                     hazard):
    import shutil
    root = str(tmp_path / "cas")
    store = _handle(kind, root)
    if hazard == "missing_root":
        shutil.rmtree(root, ignore_errors=True)
        assert list(store.walk()) == [] and store.keys() == []
        assert not os.path.exists(root)          # reading made nothing
        return
    names = ["aa01", "aa02", "bb01", "cc01"]
    for name in names:
        store.put(name, name.encode())
    if hazard == "stray_staging":
        with open(os.path.join(root, "_tmp", "aa03.1.0"), "wb") as f:
            f.write(b"half written")
        assert sorted(n for n, _, _ in store.walk()) == names
        assert sorted(store.keys()) == names
        return
    walk = store.walk()
    first = next(walk)[0]
    if hazard == "shard_removed":
        for shard in os.listdir(root):
            if shard not in ("_tmp", first[:2]):
                shutil.rmtree(os.path.join(root, shard))
    else:
        for name in names:
            if name != first:
                store.delete(name)
    rest = [n for n, _, _ in walk]               # does not raise
    assert set(rest) <= set(names) - {first}


def test_cas_offline_handle_touches_nothing_until_it_writes(tmp_path):
    root = str(tmp_path / "nowhere" / "cas")
    store = cas_mod.CASDir(root)
    assert list(store.walk()) == [] and store.recency() == {}
    assert store.seed_state() is None
    with pytest.raises(FileNotFoundError):
        store.read("abcd")
    store.delete("abcd")
    assert not os.path.exists(os.path.dirname(root))


def test_cas_store_for_root_is_live_while_one_is_open(tmp_path):
    """"The store for this root" is the registered live store, found by
    real path; otherwise the bare directory. A delete through it drops
    the live store's recency entry."""
    root = str(tmp_path / "cas")
    live = CASStore(root)
    (name, data), (name2, data2) = _entries(2)
    assert type(cas_mod.store_for(root)) is cas_mod.CASDir
    cas_mod.register_live(live)
    try:
        link = str(tmp_path / "link")
        os.symlink(root, link)
        assert cas_mod.store_for(root) is live
        assert cas_mod.store_for(link) is live
        assert cas_mod.live_stores().count(live) == 1
        assert cas_mod.live_stores({os.path.realpath(root)}) == [live]
        assert cas_mod.live_stores({str(tmp_path)}) == []
        cas_mod.store_for(root).put(name, data)
        cas_mod.CASDir(root).put(name2, data2)   # behind its back
        assert set(live.recency()) == {name}
        assert live.recency() is not live._last_access
        cas_mod.store_for(root).delete(name)
        assert name not in live._last_access and not live.exists(name)
        assert live.seed_state()["state"] == "seeded"
    finally:
        with cas_mod._live_lock:
            cas_mod._live.pop(os.path.realpath(root), None)
    assert type(cas_mod.store_for(root)) is cas_mod.CASDir
    assert sorted(cas_mod.store_for(root).keys()) == [name2]


def test_cas_layout_is_spelled_in_one_file():
    """The seam: no module but ``storage/cas.py`` joins a shard into a
    path, and none reaches the private state the old call sites used."""
    import re
    pkg = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "makisu_tpu")
    owner = os.path.join(pkg, "storage", "cas.py")
    banned = re.compile(
        r"_SHARD_CHARS|_walk_cas|_put_chunk|_live_chunk_store"
        r"|cas\._last_access|cas\._tmp_dir|cas\._path")
    found = []
    for dirpath, _, files in os.walk(pkg):
        for fn in files:
            path = os.path.join(dirpath, fn)
            if not fn.endswith(".py") or path == owner:
                continue
            with open(path, encoding="utf-8") as f:
                lines = f.read().splitlines()
            for i, line in enumerate(lines):
                near = " ".join(lines[max(0, i - 1):i + 2])
                if banned.search(line) or (
                        "[:2]" in line and "join(" in near):
                    found.append(f"{os.path.relpath(path, pkg)}:"
                                 f"{i + 1}: {line.strip()}")
    assert found == []
    with open(owner, encoding="utf-8") as f:
        assert "_SHARD_CHARS" in f.read()        # the scan sees the owner
