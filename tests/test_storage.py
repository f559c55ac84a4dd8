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
    """The tree loose ``entries`` make: mode 0600 files, an empty
    ``_tmp/``."""
    tree = {os.path.join(name[:2], name): (0o600, data)
            for name, data in entries}
    tree["_tmp/"] = (0, b"")
    return tree


def _holds(store_tree, root: str, entries, loose=()) -> None:
    """``root`` holds exactly ``entries`` as segment entries and
    ``loose`` as files of their own, as a bare handle made now reads
    them: the layout is asked, no path is joined here."""
    bare = cas_mod.CASDir(root)
    want = list(entries) + list(loose)
    assert sorted((n, s) for n, s, _ in bare.walk()) == sorted(
        (n, len(d)) for n, d in want)
    assert sorted(bare.keys()) == sorted(n for n, _ in want)
    for name, data in want:
        assert bare.read(name) == data
    tree = store_tree(root)
    files = {k: v for k, v in tree.items()
             if not k.startswith("_seg") and not k.endswith("/")}
    assert files == {k: v for k, v in _stored(loose).items()
                     if not k.endswith("/")}
    if entries:
        kinds = sorted(k.rsplit(".", 1)[1] for k in tree
                       if k.startswith("_seg/"))
        assert kinds and set(kinds) == {"idx", "seg"}
        assert kinds.count("idx") == kinds.count("seg")
        # A segment is the bytes appended to it: nothing preallocated.
        assert sum(len(v[1]) for k, v in tree.items()
                   if k.endswith(".seg")) >= sum(len(d) for _, d in entries)


def _open_fds() -> int:
    return len(os.listdir("/proc/self/fd"))


def test_cas_write_many_two_writes_a_batch_none_under_lock(
        tmp_path, fs_calls, store_tree):
    """A batch is an append: one write of its payloads, then one of its
    records, into a segment pair that a fresh store creates once."""
    store = CASStore(str(tmp_path / "cas"))
    entries = _entries(40)
    fds = _open_fds()
    rec = fs_calls(store)
    assert store.write_many(entries) == {"segment": 1, "index": 1,
                                         "loose": 0}
    # The first create finds no _seg/ and makes it; then the pair.
    assert rec.calls["open"] == 3 and rec.calls["makedirs"] == 1
    assert rec.calls["write"] == 2 and rec.calls["close"] == 2
    assert rec.calls["rename"] == rec.calls["mkdir"] == 0
    assert rec.total() == 8                       # and nothing else
    assert rec.under_lock == []
    assert _open_fds() == fds                     # nothing kept open
    _holds(store_tree, store.root, entries)
    tree = store_tree(store.root)
    assert sorted(k for k in tree if k.startswith("_seg/")) and \
        len([k for k in tree if k.startswith("_seg/")]) == 2
    seg = next(v[1] for k, v in tree.items() if k.endswith(".seg"))
    assert seg == b"".join(d for _, d in entries)  # back to back, in order
    # The next batch, through this handle or another of the process,
    # creates nothing: it appends to the pair that is there.
    again = CASStore(store.root)
    more = _entries(45)[40:]
    rec = fs_calls(again)
    assert again.write_many(more) == {"segment": 0, "index": 0, "loose": 0}
    assert rec.calls["open"] == rec.calls["write"] == 2
    assert rec.calls["close"] == 2 and rec.total() == 6
    assert rec.under_lock == []
    _holds(store_tree, store.root, entries + more)
    assert len([k for k in store_tree(store.root)
                if k.startswith("_seg/")]) == 2
    # What the records say, through the handle that stored them and
    # one that read them from disk.
    for handle in (store, again):
        assert handle.size(entries[3][0]) == len(entries[3][1])
    # A miss may be stale by one refresh, a read never is.
    assert again.exists(more[0][0]) and not store.exists(more[0][0])
    assert store.read(more[0][0]) == more[0][1]
    store.refresh()
    assert all(store.exists(n) for n, _ in more)


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
    (name, data), (absent, _) = _entries(2)
    store.put(name, data)
    rec = fs_calls(store)
    assert store.exists("abcd") and store.size("abcd") == 7
    assert store.path("abcd").endswith("abcd")
    store.delete("abcd")
    assert rec.total() == 4 and rec.under_lock == []
    assert "abcd" not in store._last_access
    # A name whose shard directory was never there, and a segment
    # entry, are answered from memory: no call at all.
    rec = fs_calls(store)
    assert not store.exists("ffff") and not store.exists(absent)
    assert store.exists(name) and store.size(name) == len(data)
    assert rec.total() == 0 and rec.under_lock == []


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
    _holds(store_tree, store.root, entries)
    assert set(store._last_access) == {name for name, _ in entries}


def test_cas_write_many_failure_leaves_no_partial_entry(
        tmp_path, fs_calls, store_tree):
    import errno
    store = CASStore(str(tmp_path / "cas"))
    entries = _entries(10)
    store.write_many(entries[:3])
    fds = _open_fds()
    rec = fs_calls(store)
    # The second write of the call is the records': the payloads are in
    # the segment, nothing names them.
    rec.fail_at["write"] = (2, OSError(errno.ENOSPC, "no space"))
    with pytest.raises(OSError):
        store.write_many(entries[3:])
    assert _open_fds() == fds
    # Three stored and recorded, the failed batch gone without a name.
    _holds(store_tree, store.root, entries[:3])
    assert sorted(store._last_access) == sorted(n for n, _ in entries[:3])
    assert not any(store.exists(n) for n, _ in entries[3:])
    # The segment that failed is not appended to again.
    store.write_many(entries[3:])
    _holds(store_tree, store.root, entries)
    assert len([k for k in store_tree(store.root)
                if k.endswith(".seg")]) == 2


def test_cas_rename_remakes_a_shard_that_went_away(tmp_path):
    import shutil
    store = CASStore(str(tmp_path / "cas"))
    store.write_bytes("aa01", b"one")
    shutil.rmtree(os.path.join(store.root, "aa"))
    store.write_bytes("aa02", b"two")
    assert store.exists("aa02") and not store.exists("aa01")
    # The same for the segments: a directory that went away with a
    # segment this process would have appended to is made again.
    (name, data), (name2, data2) = _entries(2)
    store.write_many([(name, data)])
    shutil.rmtree(os.path.join(store.root, "_seg"))
    store.write_many([(name2, data2)])
    assert store.read(name2) == data2
    with pytest.raises(FileNotFoundError):
        store.read(name)
    assert not store.exists(name)


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



# -- segments (PR 49) ---------------------------------------------------------


def _seg_files(root: str, ext: str) -> list[str]:
    """Paths of the segment files of one kind, asked of the owner's
    directory attribute: the one place a test may learn it from."""
    seg_dir = cas_mod.CASDir(root)._seg_dir
    if not os.path.isdir(seg_dir):
        return []
    return sorted(os.path.join(seg_dir, n) for n in os.listdir(seg_dir)
                  if n.endswith(ext))


@pytest.mark.parametrize("kind", ["live", "offline"])
def test_cas_bare_handle_made_before_an_append_reads_it(tmp_path, kind):
    """A record is written after its bytes, so a handle that sees the
    record reads the entry: one made before the append (on a root that
    was not even there) finds it at its first miss."""
    root = str(tmp_path / "cas")
    early = cas_mod.CASDir(root)
    assert early.keys() == []                    # its index is read: empty
    writer = _handle(kind, root)
    entries = _entries(6)
    writer.write_many(entries[:3])
    assert early.read(entries[0][0]) == entries[0][1]
    writer.write_many(entries[3:])
    with early.open(entries[5][0]) as f:
        assert f.read() == entries[5][1]
    assert sorted(n for n, _, _ in early.walk()) == sorted(
        n for n, _ in entries)
    assert early.where(entries[4][0]).rsplit("@", 1)[1] == "%d+%d" % (
        sum(len(d) for _, d in entries[:4]), len(entries[4][1]))


@pytest.mark.parametrize("torn", [1, 31, 55])
def test_cas_torn_index_tail_and_unreferenced_bytes_are_ignored(
        tmp_path, store_tree, torn):
    """What a crash leaves: bytes at a segment's tail that no record
    names, a last record cut short. Neither is read, and the next
    appends and deletes land whole."""
    root = str(tmp_path / "cas")
    store = CASStore(root)
    entries = _entries(8)
    store.write_many(entries[:5])
    (seg,), (idx,) = _seg_files(root, ".seg"), _seg_files(root, ".idx")
    record = cas_mod._REC.pack(bytes.fromhex(entries[5][0]), 1500, 300,
                               cas_mod._ENTRY, 1.0)
    with open(seg, "ab") as f:
        f.write(b"half a batch whose records never came")
    with open(idx, "ab") as f:
        f.write(record[:torn])
    for handle in (cas_mod.CASDir(root), CASStore(root)):
        assert sorted(handle.keys()) == sorted(n for n, _ in entries[:5])
        with pytest.raises(FileNotFoundError):
            handle.read(entries[5][0])
    # Whoever appends next, a batch or a tombstone, pads the torn
    # record to a whole void one and lands behind the stray bytes.
    if torn == 31:
        cas_mod.CASDir(root).delete(entries[0][0])
        assert os.path.getsize(idx) % cas_mod._REC.size == 0
        store.write_many(entries[6:])
    else:
        store.write_many(entries[6:])
        assert os.path.getsize(idx) % cas_mod._REC.size == 0
        cas_mod.CASDir(root).delete(entries[0][0])
    kept = entries[1:5] + entries[6:]
    store.refresh()                  # a hit is as fresh as the refresh
    for handle in (store, cas_mod.CASDir(root), CASStore(root)):
        for name, data in kept:
            assert handle.read(name) == data
        with pytest.raises(FileNotFoundError):
            handle.read(entries[0][0])


@pytest.mark.parametrize("under", ["segment", "loose"])
@pytest.mark.parametrize("kind", ["live", "offline"])
def test_cas_put_over_a_stored_name_reads_the_new_bytes(
        tmp_path, kind, under):
    """The newest record of a name is the one read, also over a loose
    file of that name and through a handle that read the old one."""
    root = str(tmp_path / "cas")
    live = CASStore(root)
    (name, data), (other, data2) = _entries(2)
    if under == "segment":
        live.put(name, data)
    else:
        live.write_bytes(name, data)
    live.put(other, data2)
    assert live.read(name) == data
    altered = bytes([data[0] ^ 1]) + data[1:]
    _handle(kind, root).put(name, altered)
    for handle in (cas_mod.CASDir(root), CASStore(root)):
        assert handle.read(name) == altered
        assert handle.read(other) == data2
        assert [n for n, _, _ in handle.walk()].count(name) == 1
    live.refresh()
    assert live.read(name) == altered and live.size(name) == len(altered)
    # Deleting the name deletes every copy of it.
    cas_mod.CASDir(root).delete(name)
    for handle in (cas_mod.CASDir(root), CASStore(root)):
        assert sorted(handle.keys()) == [other]
        with pytest.raises(FileNotFoundError):
            handle.read(name)


@pytest.mark.parametrize("kind", ["live", "offline"])
def test_cas_delete_retires_reclaims_and_rewrites_segments(
        tmp_path, store_tree, kind):
    root = str(tmp_path / "cas")
    store = _handle(kind, root)
    first, second = _entries(10), _entries(30)[10:]
    store.write_many(first)
    (seg_a,) = _seg_files(root, ".seg")
    # A second segment: the first is taken while the second call runs.
    slot = cas_mod._take_segment(root)
    store.write_many(second)
    cas_mod._give_segment(root, slot)
    assert len(_seg_files(root, ".seg")) == 2
    # Fewer than half of a segment dead: tombstones, nothing moves.
    for name, _ in first[:4]:
        store.delete(name)
    assert seg_a in _seg_files(root, ".seg")
    if kind == "live":
        assert not any(store.exists(n) for n, _ in first[:4])
    for handle in (store, cas_mod.CASDir(root)):
        with pytest.raises(FileNotFoundError):
            handle.read(first[0][0])
        assert sorted(n for n, _, _ in handle.walk()) == sorted(
            n for n, _ in first[4:] + second)
    stamps = {n: t for n, _, t in store.walk()}
    # The delete that tips it past half rewrites its live entries
    # elsewhere, stamps kept, and unlinks the pair.
    store.delete(first[4][0])
    store.delete(first[5][0])
    assert seg_a not in _seg_files(root, ".seg")
    assert not os.path.exists(seg_a[:-4] + ".idx")
    _holds(store_tree, root, first[6:] + second)
    assert {n: t for n, _, t in cas_mod.CASDir(root).walk()} == {
        n: t for n, t in stamps.items()
        if n not in (first[4][0], first[5][0])}
    # Every entry dead: every segment gone, and the disk with them.
    for name, _ in first[6:] + second:
        store.delete(name)
    assert _seg_files(root, ".seg") == _seg_files(root, ".idx") == []
    assert store.keys() == [] and list(store.walk()) == []
    # And the store takes entries again.
    store.put(*first[0])
    assert cas_mod.CASDir(root).read(first[0][0]) == first[0][1]


def test_cas_disk_is_bounded_by_what_walk_reports(tmp_path, store_tree):
    """Deletes in any order leave at most about twice the live bytes
    (plus the indexes) on disk: a segment more than half dead does not
    stay."""
    import random
    root = str(tmp_path / "cas")
    store = CASStore(root)
    entries = _entries(200, size=600)
    for i in range(0, 200, 25):
        store.write_many(entries[i:i + 25])
    order = list(entries)
    random.Random(7).shuffle(order)
    for n, (name, _) in enumerate(order[:180]):
        store.delete(name)
        if n % 20 == 19:
            live = sum(size for _, size, _ in store.walk())
            on_disk = sum(os.path.getsize(p)
                          for p in _seg_files(root, ".seg"))
            assert live <= on_disk <= 2 * live + 600
    _holds(store_tree, root, order[180:])


def test_cas_path_and_link_out_of_a_segment_entry(tmp_path, store_tree):
    """``path`` hands out a file: a segment entry is made a loose one
    first and stays one entry under its name."""
    store = CASStore(str(tmp_path / "cas"))
    entries = _entries(4)
    store.write_many(entries)
    name, data = entries[1]
    p = store.path(name)
    with open(p, "rb") as f:
        assert f.read() == data
    assert store.path(name) == p                 # loose now: as before
    dst = tmp_path / "out" / "copy.bin"
    store.link_out(entries[2][0], str(dst))
    assert dst.read_bytes() == entries[2][1]
    _holds(store_tree, store.root, [entries[0], entries[3]],
           loose=[entries[1], entries[2]])
    with pytest.raises(FileNotFoundError):
        store.path("0" * 64)
    # First writer wins sees a segment entry as it sees a loose one.
    store.write_bytes(entries[0][0], b"other bytes")
    assert store.read(entries[0][0]) == entries[0][1]
    assert os.listdir(store._tmp_dir) == []
    store.delete(name)
    assert not store.exists(name) and not os.path.exists(p)


def _old_sequence_store(root: str, entries) -> None:
    """A store as the parent commit's ``write_many`` left it: one file
    an entry at ``<aa>/<name>``, an empty ``_tmp/``, no segments (made
    by hand, as ``perfbench/tests/test_check.py`` makes its own)."""
    os.makedirs(os.path.join(root, "_tmp"))
    for name, data in entries:
        os.makedirs(os.path.join(root, name[:2]), exist_ok=True)
        with open(os.path.join(root, name[:2], name), "wb") as f:
            f.write(data)
        os.chmod(f.name, 0o600)


def test_cas_store_written_by_the_old_sequence_serves_as_before(
        tmp_path, store_tree, fs_calls):
    root = str(tmp_path / "cas")
    entries = _entries(12)
    _old_sequence_store(root, entries)
    os.chmod(root, 0o755)
    before = store_tree(root)
    bare = cas_mod.CASDir(root)
    assert sorted((n, s) for n, s, _ in bare.walk()) == sorted(
        (n, len(d)) for n, d in entries)
    assert all(bare.read(n) == d for n, d in entries)
    store = CASStore(root, max_entries=8)
    assert all(store.exists(n) and store.size(n) == len(d)
               for n, d in entries)
    assert store_tree(root) == before            # reading wrote nothing
    assert store.where(entries[0][0]) == os.path.join(
        root, entries[0][0][:2], entries[0][0])
    # One open a read, as before: the open is the existence check.
    rec = fs_calls(store)
    assert store.read(entries[0][0]) == entries[0][1]
    assert dict(rec.calls) == {"open_read": 1}
    # Deleting and evicting unlink the files.
    store.delete(entries[0][0])
    assert not store.exists(entries[0][0])
    for i, (name, _) in enumerate(entries[1:]):
        store._last_access[name] = float(i)
    with store._lock:
        store._evict_locked()
    assert sorted(store.keys()) == sorted(n for n, _ in entries[4:])
    assert sorted(k for k in store_tree(root) if not k.endswith("/")) == \
        sorted(os.path.join(n[:2], n) for n, _ in entries[4:])
    # New entries go to a segment beside them; both forms are served.
    more = _entries(14)[12:]
    store.write_many(more)
    _holds(store_tree, root, more, loose=entries[6:])


def test_cas_evict_locked_with_pins_over_segment_entries(tmp_path,
                                                         store_tree):
    """Eviction deletes through the layout: victims that are segment
    entries get tombstones, a pinned one is passed over, and a segment
    left more than half dead goes."""
    store = CASStore(str(tmp_path / "cas"), max_entries=100)
    entries = _entries(20)
    store.write_many(entries)
    store.write_bytes("aa-loose", b"a file of its own")
    (seg,) = _seg_files(store.root, ".seg")
    pinned = entries[0][0]
    store.pin_check = lambda name: name == pinned
    for i, (name, _) in enumerate(entries):
        store._last_access[name] = float(i)      # the pinned one oldest
    store._last_access["aa-loose"] = 5.5
    store.max_entries = 8
    with store._lock:
        store._evict_locked()
    kept = [entries[0]] + entries[13:]
    assert sorted(store.keys()) == sorted(n for n, _ in kept)
    assert set(store._last_access) == {n for n, _ in kept}
    assert seg not in _seg_files(store.root, ".seg")    # 12 of 20 dead
    _holds(store_tree, store.root, kept)
    assert all(store.exists(n) for n, _ in kept)
    assert not store.exists(entries[1][0])


def test_cas_two_handles_eight_threads_append_at_once(tmp_path,
                                                      store_tree):
    """Two handles of one process on one root and eight threads: no
    two calls write one segment at once, nothing is lost, no descriptor
    stays open, and the files stay a few."""
    import sys
    root = str(tmp_path / "cas")
    handles = [CASStore(root, 4096), CASStore(root, 4096)]
    entries = _entries(640, size=1200)
    barrier = threading.Barrier(8)
    errors = []
    fds = _open_fds()

    def work(k):
        try:
            barrier.wait(timeout=10)
            mine = entries[k::8]
            for i in range(0, len(mine), 8):
                handles[k % 2].write_many(mine[i:i + 8])
        except Exception as e:  # pragma: no cover
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not errors and not any(t.is_alive() for t in threads)
    assert _open_fds() == fds
    _holds(store_tree, root, entries)
    assert 1 <= len(_seg_files(root, ".seg")) <= 8
    for handle in handles:
        handle.refresh()
        assert all(handle.exists(n) for n, _ in entries)


def test_cas_a_dropped_store_leaves_no_descriptor(tmp_path):
    """Reads, appends, deletes and a reclaim: every descriptor is the
    call's own."""
    import gc
    fds = _open_fds()
    store = CASStore(str(tmp_path / "cas"))
    entries = _entries(30)
    store.write_many(entries)
    bare = cas_mod.CASDir(store.root)
    assert all(bare.read(n) == d for n, d in entries)
    for name, _ in entries[:20]:
        store.delete(name)
    store.path(entries[25][0])
    list(store.walk())
    assert _open_fds() == fds
    del store, bare
    gc.collect()
    assert _open_fds() == fds


def test_cas_a_segment_rolls_at_its_size(tmp_path, monkeypatch):
    monkeypatch.setattr(cas_mod, "_SEGMENT_BYTES", 1000)
    store = CASStore(str(tmp_path / "cas"))
    entries = _entries(12)                       # 300 bytes each
    for entry in entries:
        store.put(*entry)
    segs = _seg_files(store.root, ".seg")
    assert len(segs) == 3                        # four entries fill one
    assert all(os.path.getsize(p) == 1200 for p in segs)
    assert all(cas_mod.CASDir(store.root).read(n) == d
               for n, d in entries)


def test_cas_a_name_no_record_can_hold_is_stored_loose(tmp_path,
                                                       store_tree):
    """What decides the form is the ingest path, and on the bulk path
    whether a record can name the entry: 64 lower-case hex digits."""
    store = CASStore(str(tmp_path / "cas"))
    (name, data), = _entries(1)
    odd = [("aa01", b"short name"), (name.upper(), b"not lower case"),
           ("z" * 64, b"not hex")]
    assert store.write_many([(name, data)] + odd) == {
        "segment": 1, "index": 1, "loose": 3}
    _holds(store_tree, store.root, [(name, data)], loose=odd)


def test_cas_a_hit_whose_segment_has_gone_raises_as_a_vanished_file(
        tmp_path):
    """Another handle (another process's evictor) deleted everything
    and the segment went with it: this handle's stale hit raises what a
    vanished loose file raises, and it has heard of the rest by then."""
    root = str(tmp_path / "cas")
    store = CASStore(root)
    entries = _entries(6)
    store.write_many(entries)
    assert all(store.exists(n) for n, _ in entries)
    other = cas_mod.CASDir(root)
    for name, _ in entries:
        other.delete(name)
    assert _seg_files(root, ".seg") == []
    assert store.exists(entries[0][0])           # stale until it looks
    with pytest.raises(FileNotFoundError):
        store.read(entries[0][0])
    assert not any(store.exists(n) for n, _ in entries)
    store.delete(entries[1][0])                  # absent: not an error
    # A reclaim that moved live entries is followed, not lost.
    store.write_many(entries)
    reader = CASStore(root)
    assert reader.read(entries[5][0]) == entries[5][1]
    for name, _ in entries[:4]:
        cas_mod.CASDir(root).delete(name)        # tips it: rewritten
    assert reader.read(entries[5][0]) == entries[5][1]
    assert reader.read(entries[4][0]) == entries[4][1]
    with pytest.raises(FileNotFoundError):
        reader.read(entries[0][0])


_OTHER_PROCESS = """
import hashlib, sys, time
sys.path.insert(0, {checkout!r})
from makisu_tpu.storage import cas
root, role, n = sys.argv[1], sys.argv[2], int(sys.argv[3])
store = cas.CASDir(root)
def entry(i):
    data = (b"%06d" % i) * 150
    return hashlib.sha256(data).hexdigest(), data
if role == "append":
    for i in range(0, n, 5):
        store.write_many([entry(j) for j in range(i, i + 5)])
else:
    # Delete two entries of three as soon as they show: segments tip
    # and are rewritten and unlinked under the appender.
    left = {{i for i in range(n) if i % 3}}
    deadline = time.monotonic() + 60
    while left and time.monotonic() < deadline:
        store.refresh()
        for i in sorted(left):
            if store._lookup(entry(i)[0]) is not None:
                store.delete(entry(i)[0])
                left.discard(i)
    sys.exit(1 if left else 0)
"""


def test_cas_two_processes_append_and_reclaim_lose_nothing(tmp_path):
    """One process appends, another deletes most of what appears (so
    segments are rewritten and unlinked while the first still takes
    them to append to), a third appends beside them: every entry that
    was not deleted reads its bytes, every deleted one is gone."""
    import hashlib
    import subprocess
    import sys
    root = str(tmp_path / "cas")
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = tmp_path / "other.py"
    script.write_text(_OTHER_PROCESS.format(checkout=checkout))
    n = 400
    procs = [subprocess.Popen([sys.executable, str(script), root, role,
                               str(n)])
             for role in ("append", "delete")]
    mine = _entries(150, size=1200)            # other bytes than theirs
    store = CASStore(root, 4096)
    for i in range(0, len(mine), 5):
        store.write_many(mine[i:i + 5])
    assert [p.wait(timeout=120) for p in procs] == [0, 0]
    bare = cas_mod.CASDir(root)
    held = {name: size for name, size, _ in bare.walk()}
    for i in range(n):
        data = (b"%06d" % i) * 150
        name = hashlib.sha256(data).hexdigest()
        if i % 3:
            assert name not in held
        else:
            assert bare.read(name) == data
    assert all(bare.read(name) == data for name, data in mine)
    assert len(held) == len(range(0, n, 3)) + len(mine)
    # Segments were reclaimed on the way: the appender's 360,000 bytes
    # are not all still there.
    assert sum(os.path.getsize(p) for p in _seg_files(root, ".seg")) < \
        900 * n // 2 + 1200 * len(mine)
    store.refresh()
    assert all(store.read(name) == data for name, data in mine)


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
        _holds(store_tree, root, entries)
        # The other kind of handle over the same root adds to it.
        other = _handle("offline" if kind == "live" else "live", root)
        more = _entries(15)[12:]
        for name, data in more:
            other.put(name, data)
        _holds(store_tree, root, entries + more)
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
        _holds(store_tree, root, kept)
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
        r"|cas\._last_access|cas\._tmp_dir|cas\._path"
        r"|_seg\b|_seg_dir|_seg_path|\.seg\b|\.idx\b|_REC\b"
        r"|cas\._index|cas\._segments|_free_segments")
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
        text = f.read()
    # The scan sees the owner, for the old names and the new.
    assert "_SHARD_CHARS" in text and len(banned.findall(text)) > 20
