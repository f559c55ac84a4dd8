"""Thread-safe content-addressed store with LRU eviction.

Reference capabilities covered: lib/storage/layer_tar_store.go (CAS by hex
digest, download→cache state transition, hardlink in/out, LRU 256) and the
generic machinery under lib/storage/base/ (atomic state transitions,
last-access tracking, sharded dirs). Implementation is original: one class,
atomic os.rename commits out of a staging directory, one mutex around the
in-memory recency map, eviction by persisted last-access time.

This module owns the on-disk layout: nothing else in the program builds
an entry's path or lists a CAS directory. An entry has one of two
forms, and every query answers for both:

- **loose**: a file of its own at ``<root>/<aa>/<name>``, staged under
  ``<root>/_tmp/`` and renamed into place. What ``write_file``,
  ``write_bytes`` and ``link_file`` make (arbitrary names, first writer
  wins: layer blobs, configs, packs), and what a store written before
  segments existed, or by hand, holds.
- **segment**: a span of ``<root>/_seg/<unique>.seg`` named by a
  56-byte record of ``<root>/_seg/<unique>.idx`` (``_REC``: the 32
  digest bytes, offset, length, kind, stamp). What the bulk ingest of
  digest-named entries makes (``write_many``, and ``put`` through it):
  one ``write`` of a batch's payloads, then one ``write`` of its
  records, into files that are already there.

The form follows the ingest path the caller took, nothing else.
``CASDir`` is the layout alone, usable on a store no process has open;
``CASStore`` adds recency, the entry cap and pins; ``store_for(root)``
hands out whichever this process has.
"""

from __future__ import annotations

import collections
import fcntl
import io
import itertools
import os
import shutil
import struct
import tempfile
import threading
import time
from typing import BinaryIO, Callable, Iterable, Iterator

from makisu_tpu.utils import pathutils

_SHARD_CHARS = 2

# -- segments ----------------------------------------------------------------

# One index record: digest, offset and length of the payload in the
# segment, kind, stamp (seconds, what ``walk`` reports as the mtime).
# A tombstone is a record too (offset and length 0) and retires the
# records of its name that precede it in the same index.
_REC = struct.Struct("<32sQIId")
_VOID, _ENTRY, _TOMBSTONE = 0, 1, 2
# A segment that has reached this size is not appended to again.
_SEGMENT_BYTES = 32 << 20

# The segments this process made and may still append to, free ones
# only, by the store's root as its handle spells it: ``[name, bytes]``.
# A call takes one out for its duration (two handles, or two threads,
# never write one file at once) and puts it back unless it is full or
# the call failed. No descriptor is kept: a slot is a name.
_free_segments: collections.OrderedDict[str, list[list]] = \
    collections.OrderedDict()
_free_lock = threading.Lock()
_FREE_ROOTS = 128     # roots remembered; a forgotten one makes new files
_segment_seq = itertools.count()
_PROCESS = os.urandom(4).hex()
# A forked child must not append where its parent does.
os.register_at_fork(after_in_child=_free_segments.clear)


def _take_segment(root: str) -> list | None:
    with _free_lock:
        free = _free_segments.get(root)
        return free.pop() if free else None


def _give_segment(root: str, slot: list) -> None:
    with _free_lock:
        _free_segments.setdefault(root, []).append(slot)
        _free_segments.move_to_end(root)
        while len(_free_segments) > _FREE_ROOTS:
            _free_segments.popitem(last=False)


class _Segment:
    """What a handle knows of one segment: how far it has read its
    index, the payload bytes its records name, and those of them that
    are the newest live record of their name (the rest is dead)."""

    __slots__ = ("name", "loaded", "total", "live")

    def __init__(self, name: str) -> None:
        self.name = name
        self.loaded = self.total = self.live = 0


def _key(name: str) -> bytes | None:
    """The 32 bytes a 64-digit lower-case hex name stands for; None for
    any other name, which no segment can hold."""
    if len(name) != 64:
        return None
    try:
        key = bytes.fromhex(name)
    except ValueError:
        return None
    return key if key.hex() == name else None


def _write_all(fd: int, data) -> None:
    view = memoryview(data).cast("B")
    while view:
        view = view[os.write(fd, view):]


def _write_records(idx_fd: int, records: list[bytes]) -> tuple[int, int]:
    """Append whole records to an index whose segment the caller has
    locked (every appender has, so the size stands). A torn tail (a
    writer died mid-record) is padded to a whole void record first, so
    what follows stays aligned. One ``write``. Returns the index's
    size before and after."""
    size = os.fstat(idx_fd).st_size
    data = bytes(-size % _REC.size) + b"".join(records)
    _write_all(idx_fd, data)
    return size, size + len(data)


class _FdWriter:
    """The ``write`` half of a file object over a raw descriptor: what
    ``write_file``'s callback needs, without ``os.fdopen``'s ``fstat``
    and buffer (every call here is a round trip on a network file
    system, and a chunk is one ``write``)."""

    def __init__(self, fd: int) -> None:
        self._fd = fd

    def write(self, data) -> int:
        _write_all(self._fd, data)
        return memoryview(data).nbytes


class CASDir:
    """The layout of one CAS directory: loose entries at
    ``root/<aa>/<name>`` (staged under ``root/_tmp/`` and renamed into
    place), segment entries in ``root/_seg/`` (module docstring). It
    keeps no recency and enforces no cap, so it serves a directory no
    store in this process has open (census, scrub, the evictor, tier
    refetch).

    Constructing one touches nothing on disk, and reading through one
    never creates or lists the root: the segment index is read at the
    first query, ``walk`` yields nothing for a root that is not there,
    skips a shard that is deleted under it and an entry that vanishes
    before its ``stat``. ``put`` makes what it needs the first time it
    is refused. No method hands out an entry's path; ``where`` names
    the place for a finding a person will read.

    **Loose ingest** (``CASStore.write_file`` / ``write_bytes`` /
    ``link_file``, and a ``write_many`` entry no record can name):
    create ``_tmp/<name>.<pid>.<n>`` with ``O_CREAT|O_EXCL``, write,
    close, rename onto ``<aa>/<name>``. The staging name is unique per
    process and call, so nothing probes for it and it is unlinked only
    when the sequence fails. A shard directory is made the first time
    this handle meets it (``_shards``); a rename that reports ``ENOENT``
    (someone removed the directory) makes it again and retries once.

    **Segment ingest** (``write_many``): a call takes a segment of this
    process that nobody is writing (``_take_segment``; a new pair
    ``<pid>-<process>-<n>.seg`` / ``.idx`` where there is none), opens
    it, locks it (``flock``, never waiting), writes the joined payloads
    with one ``write`` and then the records with one ``write``, closes
    both descriptors. **A record is written after the bytes it points
    at**: whoever sees a record can read its bytes; a crash between the
    two writes leaves unreferenced bytes at the segment's tail, and a
    torn last record is ignored by its length. No ``fsync`` anywhere.

    **Reading.** The index is kept in memory (``_index``: the newest
    live record of each name; ``_shadowed``: older live copies, which a
    stale miss or a ``put`` over a stored name leaves). The newest
    record of a name is the one read, also over a loose file of that
    name. A miss may be stale against other handles' appends by one
    ``refresh`` (one listing of ``_seg/``, a read of the indexes that
    grew); ``open`` refreshes before it reports a miss.

    **Reclaim.** ``delete`` appends a tombstone to the index of the
    segment that holds the entry, under that segment's lock. A segment
    left with no live entry is unlinked with its index; one more than
    half dead (in payload bytes) is rewritten by the delete that tipped
    it: its live entries appended elsewhere, stamps kept, then the
    unlink. A writer that finds its segment locked or gone takes
    another, so no append is lost to a reclaim.
    """

    # Whether ``_shards`` names every shard directory there was when
    # the handle was made (a live store lists its root once).
    _listed = False

    def __init__(self, root: str) -> None:
        self.root = root
        self._tmp_dir = os.path.join(root, "_tmp")
        self._seg_dir = os.path.join(root, "_seg")
        # Shard directories this handle has made or seen.
        self._shards: set[str] = set()
        self._stage_seq = itertools.count()
        # name key -> (segment, offset, length, stamp); None until read.
        self._index: dict[bytes, tuple] | None = None
        self._shadowed: dict[bytes, list[tuple]] = {}
        self._segments: dict[str, _Segment] = {}
        self._index_lock = threading.Lock()

    @staticmethod
    def _shard(name: str) -> str:
        return name[:_SHARD_CHARS] if len(name) > _SHARD_CHARS else "__"

    def _path(self, name: str) -> str:
        return os.path.join(self.root, self._shard(name), name)

    def _seg_path(self, seg_name: str, ext: str = ".seg") -> str:
        return os.path.join(self._seg_dir, seg_name + ext)

    def _may_be_loose(self, name: str) -> bool:
        """False where no loose file of this name can be there: its
        shard directory was not among those a live store listed."""
        return not self._listed or self._shard(name) in self._shards

    def where(self, name: str) -> str:
        """The entry's place, for a finding or a log line: to be read,
        never opened. A segment entry reads ``<segment>@<offset>+<n>``."""
        rec = self._lookup(name)
        if rec is not None:
            return f"{self._seg_path(rec[0].name)}@{rec[1]}+{rec[2]}"
        return self._path(name)

    # What a store with a recency map does on access, on commit and on
    # delete; a bare directory has none to keep.

    def _touch(self, name: str) -> None:
        pass

    def _admit(self, names: Iterable[str]) -> None:
        pass

    def _forget(self, name: str) -> None:
        pass

    def recency(self) -> dict[str, float]:
        """Last-access times this process has seen, by name: none for a
        bare directory, whose file mtimes are all there is."""
        return {}

    def seed_state(self) -> dict | None:
        """None: no recency map to seed (mtimes on disk are complete)."""
        return None

    # -- the segment index --------------------------------------------------

    def _lookup(self, name: str) -> tuple | None:
        """The newest live record of ``name`` this handle knows: a
        dictionary lookup, no system call once the index is read."""
        key = _key(name)
        return None if key is None else self._known().get(key)

    def _known(self) -> dict:
        """The index as this handle has it, read first if it has none."""
        index = self._index
        return self._load() if index is None else index

    def _load(self) -> dict:
        """The index, read now (and again if someone dropped it
        meanwhile: ``_index = None`` is how a handle says "a segment I
        knew is gone")."""
        while True:
            self.refresh()
            index = self._index
            if index is not None:
                return index

    def refresh(self) -> None:
        """Catch up with what other handles and processes did to the
        segments: one listing of ``_seg/``, a ``stat`` of each index
        this handle has read before, a read of those that are new or
        grew. Where a segment it knew is gone (reclaimed), everything
        is read again."""
        sizes: dict[str, os.DirEntry] = {}
        try:
            with os.scandir(self._seg_dir) as listing:
                for entry in listing:
                    if entry.name.endswith(".idx"):
                        sizes[entry.name[:-4]] = entry
        except OSError:
            pass  # no segments (yet), or no root
        with self._index_lock:
            index, segments = self._index, self._segments
            again = index is None or any(n not in sizes for n in segments)
            if again:
                index, segments, self._shadowed = {}, {}, {}
            for seg_name, entry in sizes.items():
                seg = segments.get(seg_name)
                try:
                    if seg is None:
                        seg = _Segment(seg_name)
                    elif entry.stat().st_size <= seg.loaded:
                        continue
                    self._apply(index, seg, self._read_tail(seg))
                except OSError:
                    continue  # unlinked under the listing
                segments[seg_name] = seg
            self._index, self._segments = index, segments

    def _read_tail(self, seg: _Segment) -> bytes:
        """An index from where this handle stopped reading it."""
        fd = os.open(self._seg_path(seg.name, ".idx"), os.O_RDONLY)
        try:
            parts = []
            at = seg.loaded
            while True:
                parts.append(os.pread(fd, 1 << 24, at))
                at += len(parts[-1])
                if len(parts[-1]) < 1 << 24:
                    return b"".join(parts)
        finally:
            os.close(fd)

    def _apply(self, index: dict, seg: _Segment, buf: bytes) -> None:
        """Take a stretch of ``seg``'s index, whole records only (a
        torn tail is read again when it has grown). A record this
        handle has taken already changes nothing."""
        whole = len(buf) - len(buf) % _REC.size
        for key, offset, length, kind, stamp in _REC.iter_unpack(
                memoryview(buf)[:whole]):
            if kind == _ENTRY:
                self._enter(index, key, (seg, offset, length, stamp))
            elif kind == _TOMBSTONE:
                self._retire(index, key, seg, stamp)
        seg.loaded += whole

    def _enter(self, index: dict, key: bytes, rec: tuple) -> None:
        """An entry record: the newest of a name is the entry, an older
        live one waits in ``_shadowed``."""
        old = index.get(key)
        if old is not None and (
                old == rec or rec in self._shadowed.get(key, ())):
            return
        seg, _, length, stamp = rec
        seg.total += length
        if old is None or stamp >= old[3]:
            index[key] = rec
            seg.live += length
            if old is None:
                return
            old[0].live -= old[2]
            rec = old
        self._shadowed.setdefault(key, []).append(rec)

    def _retire(self, index: dict, key: bytes, seg: _Segment,
                stamp: float) -> None:
        """A tombstone in ``seg``'s index: the segment's records of the
        name up to its stamp are dead; the newest live copy that is
        left, if any, is the entry."""
        best = index.get(key)
        if best is None:
            return
        left = [c for c in [best] + self._shadowed.pop(key, [])
                if c[0] is not seg or c[3] > stamp]
        heir = max(left, key=lambda c: c[3], default=None)
        if heir is not best:
            best[0].live -= best[2]
            if heir is None:
                del index[key]
                return
            heir[0].live += heir[2]
            index[key] = heir
        left.remove(heir)
        if left:
            self._shadowed[key] = left

    def _read_record(self, rec: tuple) -> bytes | None:
        """The bytes a record names; None where its segment is gone."""
        try:
            fd = os.open(self._seg_path(rec[0].name), os.O_RDONLY)
        except FileNotFoundError:
            return None
        try:
            return os.pread(fd, rec[2], rec[1])
        finally:
            os.close(fd)

    # -- queries ----------------------------------------------------------

    def _entries(self) -> Iterator[os.DirEntry]:
        """Every directory entry under a shard: loose entries, staging
        and segments left out."""
        try:
            shards = os.scandir(self.root)
        except OSError:
            return
        with shards:
            for shard in shards:
                if shard.name in ("_tmp", "_seg") or not shard.is_dir():
                    continue
                try:
                    entries = os.scandir(shard.path)
                except OSError:
                    continue  # shard deleted under us
                with entries:
                    yield from entries

    def _records(self) -> tuple[dict, list[tuple[bytes, tuple]]]:
        """The index, read now, and its live segment entries as of now."""
        index = self._load()
        with self._index_lock:
            return index, list(index.items())

    def _loose(self, index: dict) -> Iterator[os.DirEntry]:
        """Loose entries whose name no segment record holds."""
        for entry in self._entries():
            if _key(entry.name) not in index:
                yield entry

    def walk(self) -> Iterator[tuple[str, int, float]]:
        """``(name, size, mtime)`` of every live entry, each name once:
        a segment entry from its record (the stamp is its mtime), a
        loose one from one ``stat``; never a staging file."""
        index, records = self._records()
        for entry in self._loose(index):
            try:
                st = entry.stat()
            except OSError:
                continue  # deleted under us
            if entry.is_file():
                yield entry.name, st.st_size, st.st_mtime
        for key, (_, _, length, stamp) in records:
            yield key.hex(), length, stamp

    def keys(self) -> list[str]:
        index, records = self._records()
        return [entry.name for entry in self._loose(index)] \
            + [key.hex() for key, _ in records]

    def open(self, name: str) -> BinaryIO:
        """Open for reading. A segment entry is a lookup, then the
        segment's ``open``, one ``pread`` and ``close``; a loose one is
        ONE syscall on the happy path (the open itself is the existence
        check): this runs once per ~8KiB chunk when a layer applies
        straight from the chunk CAS. A miss is reported only after one
        ``refresh``: another handle may have stored the name since."""
        for fresh in (False, True):
            rec = self._lookup(name)
            if rec is not None:
                data = self._read_record(rec)
                if data is not None:
                    self._touch(name)
                    return io.BytesIO(data)
                self._index = None  # its segment is gone: read again
                continue
            try:
                f = open(self._path(name), "rb")
            except FileNotFoundError:
                if not fresh:
                    self.refresh()
                    continue
                break
            self._touch(name)
            return f
        raise FileNotFoundError(f"{name} not in store {self.root}")

    def read(self, name: str) -> bytes:
        with self.open(name) as f:
            return f.read()

    # -- ingest -----------------------------------------------------------

    def _stage_path(self, name: str) -> str:
        """A staging path no other call can hold: the entry's name, this
        process, a counter (``next`` on a count is atomic)."""
        return os.path.join(
            self._tmp_dir,
            f"{name}.{os.getpid()}.{next(self._stage_seq)}")

    def _stage(self, name: str, write: Callable[[BinaryIO], None]) -> str:
        tmp = self._stage_path(name)
        flags = os.O_CREAT | os.O_EXCL | os.O_WRONLY
        try:
            fd = os.open(tmp, flags, 0o600)
        except FileNotFoundError:
            # A bare directory nobody has written through yet.
            os.makedirs(self._tmp_dir, exist_ok=True)
            fd = os.open(tmp, flags, 0o600)
        try:
            try:
                write(_FdWriter(fd))
            finally:
                os.close(fd)
        except BaseException:
            self._remove(tmp)
            raise
        return tmp

    @staticmethod
    def _remove(path: str) -> None:
        """Unlink; a file that is not there is fine."""
        try:
            os.unlink(path)
        except FileNotFoundError:
            pass

    def _rename(self, tmp: str, dst: str) -> None:
        """Move a staged file onto its final path; on failure the
        staging file is removed and nothing shows under the name."""
        sharddir = os.path.dirname(dst)
        shard = os.path.basename(sharddir)
        try:
            if shard not in self._shards:
                try:
                    os.mkdir(sharddir)
                except FileExistsError:
                    pass
                self._shards.add(shard)
            try:
                os.rename(tmp, dst)
            except FileNotFoundError:
                # The shard directory went away under the memo.
                os.makedirs(sharddir, exist_ok=True)
                os.rename(tmp, dst)
        except BaseException:
            self._remove(tmp)
            raise

    def _write_loose(self, name: str, data: bytes) -> None:
        self._rename(self._stage(name, lambda f: f.write(data)),
                     self._path(name))

    def _loosen(self, name: str) -> str:
        """Make a segment entry a loose one (for a caller that needs a
        file): its bytes under its loose name first, then its records
        retired. Returns the loose path, whichever form the entry had."""
        if self._lookup(name) is not None:
            self._write_loose(name, self.read(name))
            self._delete_records((name,))
        return self._path(name)

    def _open_segment(self, created: dict) -> tuple[list, int, int, int]:
        """A segment for one call to append to: ``(slot, seg_fd,
        idx_fd, size)``, the segment locked. One of this process's free
        ones where it can be had at once (not locked by a delete, not
        reclaimed), else a new pair; ``created`` counts the new files."""
        busy = []
        try:
            while True:
                slot = _take_segment(self.root)
                if slot is None:
                    return self._new_segment(created)
                try:
                    seg_fd = os.open(self._seg_path(slot[0]),
                                     os.O_WRONLY | os.O_APPEND)
                except FileNotFoundError:
                    continue  # reclaimed, or the root was removed
                try:
                    fcntl.flock(seg_fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
                    st = os.fstat(seg_fd)
                    if st.st_nlink:  # else unlinked while we opened it
                        return (slot, seg_fd, os.open(
                            self._seg_path(slot[0], ".idx"),
                            os.O_WRONLY | os.O_APPEND), st.st_size)
                except BlockingIOError:
                    busy.append(slot)  # a delete holds it: not now
                except FileNotFoundError:
                    pass  # a pair without its index: nobody's to write
                except BaseException:
                    os.close(seg_fd)
                    raise
                os.close(seg_fd)
        finally:
            for slot in busy:
                _give_segment(self.root, slot)

    def _new_segment(self, created: dict) -> tuple[list, int, int, int]:
        name = f"{os.getpid():x}-{_PROCESS}-{next(_segment_seq)}"
        flags = os.O_CREAT | os.O_EXCL | os.O_WRONLY | os.O_APPEND
        try:
            seg_fd = os.open(self._seg_path(name), flags, 0o600)
        except FileNotFoundError:
            os.makedirs(self._seg_dir, exist_ok=True)
            seg_fd = os.open(self._seg_path(name), flags, 0o600)
        created["segment"] += 1
        try:
            fcntl.flock(seg_fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
            idx_fd = os.open(self._seg_path(name, ".idx"), flags, 0o600)
        except BaseException:
            os.close(seg_fd)
            raise
        created["index"] += 1
        return [name, 0], seg_fd, idx_fd, 0

    def _append(self, entries: list[tuple[bytes, bytes, float]],
                created: dict) -> None:
        """Append ``(key, data, stamp)`` entries to one segment: the
        payloads, then their records. The descriptors are this call's
        and are closed when it ends; a call that fails leaves its
        segment to the readers and never appends to it again."""
        slot, seg_fd, idx_fd, at = self._open_segment(created)
        try:
            placed = []  # (key, offset, length, stamp)
            for key, data, stamp in entries:
                placed.append((key, at, len(data), stamp))
                at += len(data)
            _write_all(seg_fd, b"".join(data for _, data, _ in entries))
            idx_was, idx_is = _write_records(idx_fd, [
                _REC.pack(key, offset, length, _ENTRY, stamp)
                for key, offset, length, stamp in placed])
        finally:
            os.close(idx_fd)
            os.close(seg_fd)
        if at < _SEGMENT_BYTES:
            slot[1] = at
            _give_segment(self.root, slot)
        with self._index_lock:
            index = self._index
            if index is None:
                return  # the next query reads it all
            seg = self._segments.get(slot[0])
            if seg is None:
                seg = self._segments[slot[0]] = _Segment(slot[0])
            for key, offset, length, stamp in placed:
                self._enter(index, key, (seg, offset, length, stamp))
            if seg.loaded == idx_was:
                seg.loaded = idx_is  # nothing of it is left to read

    def write_many(self, items: Iterable[tuple[str, bytes]]
                   ) -> dict[str, int]:
        """Bulk ingest of entries whose name is the digest of their
        bytes (the caller has verified it, and has probed that they are
        new): the batch is appended to one segment, two ``write``s
        however many entries, and one lock round for the batch. An
        entry no record can name (not 64 hex digits, or 4 GiB) is
        stored loose. No look at what is stored: identical bytes under
        one name make a second record the same outcome as none. A name
        that is stored with other bytes reads the new ones from now on.
        Returns the files the call created by kind (``segment``,
        ``index``, ``loose``). On failure nothing of the batch's
        segment entries is recorded; a loose entry that failed leaves
        nothing behind."""
        created = {"segment": 0, "index": 0, "loose": 0}
        done: list[str] = []
        names: list[str] = []
        packed: list[tuple[bytes, bytes]] = []
        stamp = time.time()
        index = self._known()
        try:
            for name, data in items:
                key = _key(name)
                if key is None or len(data) >> 32:
                    self._write_loose(name, data)
                    created["loose"] += 1
                    done.append(name)
                    continue
                old = index.get(key)
                if old is not None and old[3] >= stamp:
                    stamp = old[3] + 1e-6  # the newest record is read
                names.append(name)
                packed.append((key, data))
            if packed:
                self._append([(key, data, stamp) for key, data in packed],
                             created)
                done += names
        finally:
            if done:
                self._admit(done)
        return created

    def put(self, name: str, data: bytes) -> dict[str, int]:
        """One entry through ``write_many``: ``name`` is the digest of
        ``data``, and the caller has checked it."""
        return self.write_many(((name, data),))

    # -- delete and reclaim -------------------------------------------------

    def delete(self, name: str) -> None:
        self._delete_many((name,))
        self._forget(name)

    def _delete_many(self, names: Iterable[str]) -> None:
        """Remove entries of either form: the loose file is unlinked,
        every live record gets a tombstone in its segment's index (one
        lock round a segment), and a segment this tipped is reclaimed."""
        names = list(names)
        for name in names:
            if self._may_be_loose(name):
                self._remove(self._path(name))
        self._delete_records(names)

    def _delete_records(self, names: Iterable[str]) -> None:
        pending = [key for key in map(_key, names) if key is not None]
        for _ in range(3):
            if not pending:
                return
            index = self._known()
            by_segment: dict[str, list[bytes]] = {}
            with self._index_lock:
                for key in pending:
                    best = index.get(key)
                    for rec in ([best] if best else []) \
                            + self._shadowed.get(key, []):
                        by_segment.setdefault(rec[0].name, []).append(key)
            pending = []
            for seg_name, keys in by_segment.items():
                if not self._bury(seg_name, keys):
                    # Reclaimed by someone else: its live entries are
                    # in another segment now. Read again, then retry.
                    self._index = None
                    pending.extend(keys)

    def _bury(self, seg_name: str, keys: list[bytes]) -> bool:
        """Append tombstones for ``keys`` to one segment's index and
        reclaim the segment if that tipped it, all under its lock
        (waited for: a writer holds it for two writes, a reclaim for a
        copy). False where the segment is gone."""
        try:
            seg_fd = os.open(self._seg_path(seg_name), os.O_RDONLY)
        except FileNotFoundError:
            return False
        try:
            fcntl.flock(seg_fd, fcntl.LOCK_EX)
            if not os.fstat(seg_fd).st_nlink:
                return False
            try:
                idx_fd = os.open(self._seg_path(seg_name, ".idx"),
                                 os.O_WRONLY | os.O_APPEND)
            except FileNotFoundError:
                return False
            try:
                stamp = time.time()
                _write_records(idx_fd, [
                    _REC.pack(key, 0, 0, _TOMBSTONE, stamp)
                    for key in keys])
            finally:
                os.close(idx_fd)
            with self._index_lock:
                index, seg = self._index, self._segments.get(seg_name)
                if index is None or seg is None:
                    return True
                # What others appended since this handle last read it,
                # its own tombstones included.
                self._apply(index, seg, self._read_tail(seg))
                live = [(key, rec) for key, rec in index.items()
                        if rec[0] is seg] \
                    if seg.live * 2 < seg.total else None
            if live is not None:
                self._reclaim(seg, seg_fd, live)
            return True
        finally:
            os.close(seg_fd)

    def _reclaim(self, seg: _Segment, seg_fd: int,
                 live: list[tuple[bytes, tuple]]) -> None:
        """Under ``seg``'s lock: its live entries go to another segment
        with the stamps they have, then the pair is unlinked. The
        entries are readable at every moment: their new records are
        written before the old index goes."""
        if live:
            self._append([(key, os.pread(seg_fd, rec[2], rec[1]), rec[3])
                          for key, rec in live], {"segment": 0, "index": 0})
        self._remove(self._seg_path(seg.name, ".idx"))
        self._remove(self._seg_path(seg.name))
        self._index = None  # read again, without it


class CASStore(CASDir):
    """A ``CASDir`` a process has open: recency, an entry cap, pins.

    Names are arbitrary keys (layer hex digests in practice). Files land via
    ``write_file``/``link_file`` (loose: committed with an atomic rename)
    or ``write_many`` (segment: a record after its bytes), so readers never
    observe partial content. ``max_entries`` bounds the store;
    least-recently-used entries are evicted on overflow.
    The root and its staging directory are made and the root listed
    once for the shards already there when the store is opened; the
    segment index, where the root has one, is read by the first query
    (in a build the commit's streamed probe, on the pool: opening a
    store costs what it did). From then on ``exists`` of a segment
    entry is a lookup, and a name whose shard directory was not there
    costs no ``stat``.

    **How fresh an answer is.** A miss may be stale by one ``refresh``
    against what other handles stored or another process's loose files
    in a shard that is new; that costs a second record of identical
    bytes, never a wrong answer. A hit is as fresh as the last
    ``refresh`` (the chunk store calls it once a commit: tombstones and
    reclaims by others are seen there); a hit whose segment has gone
    raises what a vanished loose file raises.

    **Who wins a race.** ``write_file``/``write_bytes``/``link_file``
    take arbitrary names and keep first-writer-wins: one ``stat`` of the
    final path before the rename, an existing entry of either form
    stays. ``write_many`` takes entries whose name is the caller-verified
    digest of their bytes and looks at nothing: two writers of one name
    hold identical bytes, so two records of it read the same.

    **The lock** guards the recency map (``_last_access``) and eviction,
    nothing else. It is never held across a system call in ``exists``,
    ``size``, ``path``, ``open`` or any ingest path; eviction alone
    deletes under it, so a victim is chosen and removed as one step.
    """

    _listed = True

    # Stores below this cap seed their LRU map eagerly at construction
    # (a few hundred stats); at or above it — the ~1M-entry chunk CAS,
    # where the seed scan is tens of thousands of stats and was a
    # measurable warm-rebuild floor term — seeding runs on a background
    # thread armed by the first write, and eviction simply defers until
    # the scan lands (advisory LRU: a few deferred evictions cost disk
    # headroom, never correctness).
    _EAGER_SEED_BELOW = 4096

    def __init__(self, root: str, max_entries: int = 256) -> None:
        super().__init__(root)
        self.max_entries = max_entries
        self._lock = threading.Lock()
        self._last_access: dict[str, float] = {}
        # Optional pin predicate (name -> bool). A True answer keeps
        # the entry out of count-LRU victim selection — the content
        # store's refcount plane wires this so an in-flight read can
        # never lose its chunk to the entry-count cap either.
        self.pin_check = None
        os.makedirs(root, exist_ok=True)
        os.makedirs(self._tmp_dir, exist_ok=True)
        # One listdir here instead of a makedirs per commit.
        self._shards = set(os.listdir(root))
        if "_seg" not in self._shards:
            self._index = {}  # nothing to read: a fresh store
        self._seeded = False
        self._seeding = False
        if max_entries < self._EAGER_SEED_BELOW:
            for name, _, mtime in self.walk():
                self._last_access[name] = mtime
            self._seeded = True

    def _seed_async_locked(self) -> None:
        """Arm the background LRU seed (large stores). Runs at most
        once; merges on-disk mtimes under the lock, live accesses
        recorded meanwhile win, then catches up deferred eviction."""
        if self._seeded or self._seeding:
            return
        self._seeding = True

        def run() -> None:
            seed: dict[str, float] = {}
            try:
                for name, _, mtime in self.walk():
                    seed[name] = mtime
            finally:
                with self._lock:
                    for name, mtime in seed.items():
                        self._last_access.setdefault(name, mtime)
                    self._seeded = True
                    self._seeding = False
                    self._evict_locked()

        threading.Thread(target=run, daemon=True,
                         name="cas-lru-seed").start()

    def seed_state(self) -> dict:
        """Observability for the background LRU seed (PR 10's thread
        is otherwise invisible): ``state`` is ``seeded`` (recency map
        complete), ``seeding`` (scan in flight), or ``unseeded``
        (large store, seed not yet armed — it arms on first write).
        Consumers that rank objects by recency (the storage plane's
        eviction dry-run) refuse to run unless ``seeded``."""
        with self._lock:
            if self._seeded:
                state = "seeded"
            elif self._seeding:
                state = "seeding"
            else:
                state = "unseeded"
            return {"state": state,
                    "seeded_entries": len(self._last_access)}

    def _touch(self, name: str) -> None:
        with self._lock:
            self._last_access[name] = time.time()

    def _admit(self, names: Iterable[str]) -> None:
        """Record committed entries and evict the overflow: one lock
        round per call, however many names."""
        now = time.time()
        with self._lock:
            for name in names:
                self._last_access[name] = now
            self._evict_locked()

    def _forget(self, name: str) -> None:
        with self._lock:
            self._last_access.pop(name, None)

    def recency(self) -> dict[str, float]:
        with self._lock:
            return dict(self._last_access)

    # -- queries ----------------------------------------------------------

    def _stored(self, name: str) -> bool:
        return self._lookup(name) is not None or (
            self._may_be_loose(name) and os.path.isfile(self._path(name)))

    def exists(self, name: str) -> bool:
        if self._stored(name):
            self._touch(name)
            return True
        return False

    def size(self, name: str) -> int:
        rec = self._lookup(name)
        size = rec[2] if rec is not None else os.path.getsize(
            self._path(name))  # raises if absent
        self._touch(name)
        return size

    # -- ingest -----------------------------------------------------------

    def _commit(self, name: str, tmp: str) -> str:
        """First writer wins (names here are arbitrary keys): an entry
        that is already there stays, and the staged file goes."""
        dst = self._path(name)
        if self._lookup(name) is not None or os.path.isfile(dst):
            self._remove(tmp)
            self._touch(name)
            return dst
        self._rename(tmp, dst)
        self._admit((name,))
        return dst

    def write_file(self, name: str, write: Callable[[BinaryIO], None]) -> str:
        """Stream content into the store via ``write(fileobj)``; atomic."""
        return self._commit(name, self._stage(name, write))

    def write_bytes(self, name: str, data: bytes) -> str:
        return self.write_file(name, lambda f: f.write(data))

    def link_file(self, name: str, src: str) -> str:
        """Ingest an existing file by hardlink (falls back to copy across
        filesystems)."""
        tmp = self._stage_path(name)
        try:
            try:
                os.link(src, tmp)
            except OSError:
                shutil.copy2(src, tmp)
        except BaseException:
            self._remove(tmp)
            raise
        return self._commit(name, tmp)

    def mkstemp(self, prefix: str) -> tuple[int, str]:
        """``tempfile.mkstemp`` in the staging directory: beside the
        entries (one file system, so a ``link_file`` of the result can
        hardlink) and out of every walk. The caller owns the file."""
        return tempfile.mkstemp(prefix=prefix, dir=self._tmp_dir)

    # -- egress -----------------------------------------------------------

    def path(self, name: str) -> str:
        """Path of a stored file (raises FileNotFoundError if absent).
        A segment entry is made a loose one first: its bytes written
        under its loose name, then its records retired."""
        segment = self._lookup(name) is not None
        p = self._loosen(name)
        if not segment and not os.path.isfile(p):
            raise FileNotFoundError(f"{name} not in store {self.root}")
        self._touch(name)
        return p

    def link_out(self, name: str, dst: str) -> None:
        """Hardlink a stored file out to ``dst`` (copy across filesystems)."""
        src = self.path(name)
        os.makedirs(os.path.dirname(dst), exist_ok=True)
        if os.path.exists(dst):
            os.unlink(dst)
        try:
            os.link(src, dst)
        except OSError:
            shutil.copy2(src, dst)

    # -- eviction ---------------------------------------------------------

    def _evict_locked(self) -> None:
        """Evict the LRU overflow. Large stores (the ~1M-entry chunk
        CAS) evict in 10% batches: a min() scan per insert is O(n), and
        at a few hundred thousand entries the store would spend its
        time scanning access times, not storing bytes. Small stores
        (the 256-entry layer-blob cache, where every entry is a warm
        multi-hundred-MB blob) evict exactly the excess — dumping 10%
        of THOSE would force re-pulls the old one-at-a-time policy
        never did."""
        import heapq
        if not self._seeded:
            # LRU state still loading (large store, background seed):
            # defer — the seed's completion re-runs this.
            self._seed_async_locked()
            return
        if len(self._last_access) <= self.max_entries:
            return
        pool = self._last_access
        if self.pin_check is not None:
            try:
                pool = {name: ts for name, ts in
                        self._last_access.items()
                        if not self.pin_check(name)}
            # Pins advise; a broken pin_check must never block eviction,
            # so fall back to the full pool.  # check: allow(silent-swallow)
            except Exception:  # noqa: BLE001
                pool = self._last_access
        excess = len(self._last_access) - self.max_entries
        batch = excess if self.max_entries < 4096 else max(
            excess, self.max_entries // 10)
        batch = min(batch, len(pool))
        victims = heapq.nsmallest(batch, pool, key=pool.get)
        self._delete_many(victims)
        for victim in victims:
            del self._last_access[victim]


# -- the stores this process has open ----------------------------------------

# Live stores by real path of their root. A store is entered by whoever
# opens it to serve (a build's chunk dedup, the worker's serve plane);
# a root entered again replaces its entry, so the map is bounded by the
# storage roots the process has built against, not by its builds.
_live: dict[str, CASStore] = {}
_live_lock = threading.Lock()


def register_live(store: CASStore) -> None:
    with _live_lock:
        _live[pathutils.real_path(store.root)] = store


def live_stores(roots=None) -> list[CASStore]:
    """The live stores, or those among ``roots`` (real paths)."""
    with _live_lock:
        return [store for root, store in _live.items()
                if roots is None or root in roots]


def store_for(root: str) -> CASDir:
    """The store for this root: the live ``CASStore`` where this
    process has one open (its recency then hears of what is read, put
    and deleted), else the bare directory."""
    with _live_lock:
        live = _live.get(pathutils.real_path(root))
    return live if live is not None else CASDir(root)
